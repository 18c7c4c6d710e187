"""The Nyström kernels compiled for a described TPU v5e, at a real sketch size.

Nothing runs: the TPU compiler (shipped with libtpu) compiles each kernel for
a v5e that is described, not attached. That catches what interpret mode
cannot — a block the Mosaic tiling refuses, a kernel over its VMEM budget —
and ``memory_analysis()`` shows the temporaries each program allocates. A
wrapper that padded or transposed the p-sized sketch in HBM would need
gigabytes of temporaries here; reading the (k, p) buffer in place needs
almost none.

The topology is described inside a module fixture, never at import: only one
process may load libtpu at a time, and every test worker imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import nystrom_gram, woodbury

P = 1 << 24          # sketch columns: 2^24 parameters, a bf16 (k, p) sketch
M = 32               # query-block width of the block apply
TEMP_LIMIT = 64 << 20
# an HLO instruction that copies or transposes an array: its result dims
_RELAYOUT = re.compile(r'= \w+\[([\d,]*)\]\S* (?:copy|transpose)\(')


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:                       # no libtpu / no TPU target
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    'nystrom_gram': (nystrom_gram.nystrom_gram, lambda k: [(k, P)]),
    'woodbury_ctv': (woodbury.woodbury_ctv, lambda k: [(k, P), (P,)]),
    'woodbury_apply': (functools.partial(woodbury.woodbury_apply, rho=1e-2),
                       lambda k: [(k, P), (k,), (P,)]),
    'woodbury_apply_block': (
        functools.partial(woodbury.woodbury_apply, rho=1e-2),
        lambda k: [(k, P), (k, M), (M, P)]),
}


@pytest.mark.parametrize('k', [8, 64])
@pytest.mark.parametrize('name', sorted(KERNELS))
def test_kernel_compiles_for_v5e_without_p_sized_temporaries(one_chip, name,
                                                             k):
    fn, shapes = KERNELS[name]
    sketch, *rest = shapes(k)
    args = [_sds(sketch, jnp.bfloat16, one_chip)]
    args += [_sds(s, jnp.float32, one_chip) for s in rest]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert 'tpu_custom_call' in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT, (
        f'{name} k={k}: {temp / 2**20:.1f} MiB of temporaries — a p-sized '
        'copy of an operand is back')


def test_pallas_block_apply_transposes_no_query_block(one_chip):
    """PallasBackend's block apply hands the (p, m) query block to the
    kernels as (m, p). At a p the kernel grid does not divide (so the
    overhang mask compiles too), every such transpose must be a bitcast:
    no p-sized copy or transpose in the program, and no more temporaries
    than the refine sweep's two live blocks (the iterate and its residual).
    """
    from repro.core import NystromIHVP
    from repro.core.backend import PallasBackend
    from repro.core.tree_util import PyTreeIndexer

    p, k = P + 300, 8
    solver = NystromIHVP(k=k, rho=1e-2, refine=1, backend=PallasBackend(
        sketch_dtype=jnp.bfloat16, interpret=False))
    indexer = PyTreeIndexer({'w': _sds((p,), jnp.float32, one_chip)})
    sketch = jax.eval_shape(lambda: solver.prepare(
        lambda v: v, indexer, jax.random.PRNGKey(0)))
    sketch = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), sketch)
    V = {'w': _sds((p, M), jnp.float32, one_chip)}
    compiled = jax.jit(solver.apply_matrix).lower(sketch, V).compile()
    hlo = compiled.as_text()
    assert 'tpu_custom_call' in hlo
    entry = hlo[hlo.index('\nENTRY'):]
    relayouts = [m.group(0) for m in _RELAYOUT.finditer(entry)
                 if str(p) in m.group(1).split(',')]
    assert not relayouts, relayouts
    block = p * M * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2 * block + TEMP_LIMIT, (
        f'{temp / block:.2f} query blocks of temporaries, expected <= 2')
