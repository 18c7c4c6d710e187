"""Compile a configuration's trainer steps for a described v5e, without the
chip, and print what each needs in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/fit_rehearsal.py rwkv6-1.6b [n_layers ...]

The configuration is a file in `configs/`, run under `traffic/reweight.fresh`.

For each depth it builds the inner and outer step as `train_lm` does (the
same losses, optimizer, `SketchPolicy` and `implicit_root`; the outer step
donates its sketch state), lowers them on shapes alone for one described
v5e chip and prints `memory_analysis()`: the arguments resident across the
loop (parameters, AdamW state, the sketch) and the temporaries each program
reserves when it loads. The sum is what the chip has to hold at once.
"""
from __future__ import annotations

import functools
import json
import os
import sys
from pathlib import Path

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / 'src')]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402


def steps(cfg, traffic, trainer):
    """The trainer's (inner_step, outer_step, policy, hparams) for `cfg`."""
    from repro.core import SketchPolicy, config_from_cli, implicit_root
    from repro.core.tree_util import tree_norm
    from repro.launch.steps import N_DOMAINS, make_optimizer
    from repro.launch.train import build_losses
    from repro.optim import adam
    inner_loss, outer_loss = build_losses(cfg)
    optimizer, outer_opt = make_optimizer(cfg), adam(1e-2)
    hg_cfg = config_from_cli(
        trainer['solver'], flags={'k': trainer['sketch_rank'], 'rho': trainer['rho'],
                                  'sketch_refresh_every': traffic['sketch_refresh_every']},
        defaults={'k': 8, 'rho': 1e-2}, column_chunk=4)
    solver = hg_cfg.build()
    policy = SketchPolicy(solver=solver, inner_loss=inner_loss,
                          refresh_every=hg_cfg.sketch_refresh_every)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def inner_step(params, opt_state, hparams, step, batch):
        loss, grads = jax.value_and_grad(inner_loss)(params, hparams, batch)
        params, opt_state = optimizer.apply(grads, opt_state, params, step)
        return params, opt_state, step + 1, loss

    @functools.partial(jax.jit, donate_argnums=(7,))
    def outer_step(params, hparams, outer_state, step, inner_b, outer_b, key, sketch_state):
        solve = implicit_root(lambda phi, b: params, inner_loss, solver)
        sketch_state, _ = policy.refresh(sketch_state, params, hparams, inner_b, key)

        def outer_obj(phi):
            return outer_loss(solve(phi, inner_b, state=sketch_state.sketch), phi, outer_b)
        val, hg = jax.value_and_grad(outer_obj)(hparams)
        hparams, outer_state = outer_opt.apply(hg, outer_state, hparams, step)
        return hparams, outer_state, val, tree_norm(hg), sketch_state

    hparams = {'domain_logits': jax.ShapeDtypeStruct((N_DOMAINS,), jnp.float32)}
    return inner_step, outer_step, policy, optimizer, outer_opt, hparams


def rehearse(config: str, n_layers: int, device) -> dict:
    from repro.models import build_model
    from repro.distributed.ctx import activation_mesh
    import numpy as np
    from jax.sharding import AxisType, Mesh
    cell = harness.file_cell(config, 'reweight.fresh')
    conf = json.loads(json.dumps(cell.config))
    conf['program']['model_config']['n_layers'] = n_layers
    conf['num_hidden_layers'] = n_layers
    cfg = harness.program_config(conf)
    tr, trainer = cell.traffic, conf['trainer']
    inner_step, outer_step, policy, opt, outer_opt, hparams = steps(cfg, tr, trainer)
    on = SingleDeviceSharding(device)

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on), tree)

    params = place(jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0)))
    opt_state = place(jax.eval_shape(opt.init, params))
    outer_state = place(jax.eval_shape(outer_opt.init, hparams))
    hparams = place(hparams)
    B, S = tr['batch'], tr['seq']
    batch = place({'inputs': jax.ShapeDtypeStruct((B, S), jnp.int32),
                   'labels': jax.ShapeDtypeStruct((B, S), jnp.int32),
                   'domain': jax.ShapeDtypeStruct((B,), jnp.int32),
                   'mask': jax.ShapeDtypeStruct((B, S), jnp.float32)})
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=on)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=on)
    sketch = place(jax.eval_shape(policy.init_state, params, hparams, batch, key))
    out = {'n_layers': n_layers, 'p': harness.param_count(params)}
    mesh = Mesh(np.array([device]).reshape(1, 1), ('data', 'model'),
                axis_types=(AxisType.Auto,) * 2)
    with activation_mesh(mesh):
        for name, fn, args in (
                ('inner_step', inner_step, (params, opt_state, hparams, step, batch)),
                ('outer_step', outer_step, (params, hparams, outer_state, step, batch,
                                            batch, key, sketch))):
            m = fn.lower(*args).compile().memory_analysis()
            out[name] = {'argument_bytes': m.argument_size_in_bytes,
                         'output_bytes': m.output_size_in_bytes,
                         'temp_bytes': m.temp_size_in_bytes,
                         'alias_bytes': m.alias_size_in_bytes}
    resident = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(
        (params, opt_state, sketch)))
    out['resident_bytes'] = resident
    out['resident_plus_outer_temp_bytes'] = resident + out['outer_step']['temp_bytes']
    return out


def main():
    config = sys.argv[1]
    depths = [int(x) for x in sys.argv[2:]] or [
        harness.file_cell(config, 'reweight.fresh').config['num_hidden_layers']]
    topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
    for n in depths:
        print(json.dumps(rehearse(config, n, topo.devices[0])), flush=True)


if __name__ == '__main__':
    main()
