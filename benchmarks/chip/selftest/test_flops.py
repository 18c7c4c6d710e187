"""`flops.py` against XLA's own count of the same forward pass, on the CPU.

The cells' configurations, every width cut by 16 but the structure kept,
run through the trainer's model forward; XLA's `cost_analysis()` counts
every floating-point operation, norms and activations included, which
`flops.py` leaves out. Its count has to lie within MARGIN below XLA's and
never above it. At these widths the left-out elementwise work is 3.1%
(llama) and 3.6% (rwkv6) of XLA's count; at the cells' widths, 8 to 16
times wider, it is a smaller share still. One layer each: XLA counts the
body of the scan over layers once.
"""
import jax
import jax.numpy as jnp
import pytest

import flops
import harness
import tiny

MARGIN = 0.05
WIDTHS = {
    'llama': {'hidden_size': 256, 'intermediate_size': 688, 'num_attention_heads': 4,
              'num_key_value_heads': 2, 'head_dim': 64, 'vocab_size': 1000},
    'rwkv6': {'hidden_size': 256, 'attention_hidden_size': 256, 'intermediate_size': 896,
              'vocab_size': 1024, 'num_hidden_layers': 1},
}


@pytest.mark.parametrize('config', ['yi-9b', 'rwkv6-1.6b'])
def test_forward_flops_match_xla(config):
    from repro.models import build_model
    full = harness.file_cell(config, 'reweight.fresh')
    cell = tiny.tiny_cell(None, seq=128, batch=2, widths=WIDTHS[full.config['family']],
                          cell=full)
    c = cell.config
    cfg = harness.program_config(c)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S = cell.traffic['batch'], cell.traffic['seq']
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    cost = jax.jit(lambda p, t: model.forward(p, t)[0]).lower(params, tokens).compile()
    analysis = cost.cost_analysis()
    xla = (analysis[0] if isinstance(analysis, list) else analysis)['flops']
    # XLA counts a loop's body once, whatever its trip count: compare the
    # part outside the recurrence's scan
    ours = (flops.forward_flops_per_token(c, S) - flops.recurrence_flops_per_token(c)) * B * S
    assert ours <= xla
    assert ours >= (1 - MARGIN) * xla, (ours, xla)


def test_cycle_counts_every_outer_step():
    cell = tiny.tiny_cell('yi-9b.reweight.amortized')
    f = flops.cycle_flops(cell.config, cell.traffic, k=3, p=1000)
    n = cell.traffic['sketch_refresh_every']
    assert f['cycle'] == pytest.approx(
        cell.traffic['outer_every'] * n * f['inner'] + f['outer_fresh'] + (n - 1) * f['outer_reuse'])
    assert f['outer_fresh'] - f['outer_reuse'] == pytest.approx(9 * 3 * f['forward'] + 4 * 9 * 1000)
