"""Program auditor + Contract: the declarative replacement for HLO greps.

Covers the three report layers (jaxpr / lowered StableHLO / compiled HLO),
every Contract field's violation rendering, and — because the whole point
is retiring substring asserts — one legacy-vs-contract equivalence test
that runs the OLD ``txt.count('all_reduce')`` methodology and the Contract
on the same lowered program and demands they agree. The sharded structural
guarantees themselves are enforced in tests/test_block_apply.py and
tests/sharded_parity_check.py via ``repro.core.FLAT_SHARDED_CONTRACT``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import (Contract, ContractViolation, audit, audit_jaxpr,
                            canonical_collective)

PARAMS = {'w': jnp.zeros((8,)), 'm': jnp.zeros((27, 37)),
          'b': jnp.zeros((2, 2)), 's': jnp.zeros(())}


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ('model',))


def _psum_fn(mesh):
    from repro.distributed.ctx import shard_map_unchecked

    def local(x):
        return jax.lax.psum(x.sum(axis=-1), ('model',))

    return shard_map_unchecked(local, mesh, (P(None, 'model'),), P())


# ---------------------------------------------------------------- reports
class TestAudit:
    def test_psum_counted_in_every_layer(self):
        fn = _psum_fn(_mesh1())
        x = jnp.ones((4, 8))
        report = audit(fn, x, compile=True)
        assert report.sources == ('jaxpr', 'stablehlo', 'hlo')
        for src in report.sources:
            assert report.count('psum', src) == 1, src
        # aliases all resolve to the same canonical kind
        for alias in ('psum', 'psum2', 'all_reduce', 'all-reduce'):
            assert canonical_collective(alias) == 'all-reduce'
            assert report.count(alias) == 1

    def test_jaxpr_record_carries_axes_and_shape(self):
        report = audit(_psum_fn(_mesh1()), jnp.ones((4, 8)))
        (rec,) = report.records('psum', 'jaxpr')
        assert rec.shape == (4,) and rec.dtype == 'float32'
        assert 'model' in rec.detail

    def test_collective_bytes_from_compiled_hlo(self):
        report = audit(_psum_fn(_mesh1()), jnp.ones((4, 8)), compile=True)
        assert report.collective_nbytes is not None
        assert report.collective_nbytes.get('all-reduce', 0) >= 4 * 4

    def test_tpu_tiled_layouts_parse_in_compiled_hlo(self):
        """TPU HLO types carry tiled layouts with parentheses
        (``{1,0:T(4,128)S(1)}``); the collective parsers must read them."""
        from repro.analysis.auditor import ProgramReport, _parse_hlo
        from repro.launch.analysis import collective_bytes
        hlo = ('  %psum.7 = f32[3,4]{1,0:T(4,128)S(1)} all-reduce(%fusion.2), '
               'channel_id=1, replica_groups={{0,1,2,3}}, '
               'to_apply=%region_0.0\n')
        report = ProgramReport(collectives=[], dots=[], host_transfers=[],
                               custom_vjp_calls=0, constants=[])
        _parse_hlo(hlo, report)
        assert report.counts('hlo') == {'all-reduce': 1}
        assert report.records('all-reduce', 'hlo')[0].shape == (3, 4)
        assert collective_bytes(hlo)['bytes']['all-reduce'] == 3 * 4 * 4

    def test_walks_sub_jaxprs(self):
        """Collectives inside scan/pjit bodies are found recursively."""
        fn = _psum_fn(_mesh1())

        def scanned(x):
            def body(c, _):
                return c + fn(x), None
            out, _ = jax.lax.scan(body, jnp.zeros((4,)), jnp.arange(3))
            return jax.jit(fn)(x) + out

        report = audit_jaxpr(jax.make_jaxpr(scanned)(jnp.ones((4, 8))))
        assert report.count('psum', 'jaxpr') == 2   # scan body + nested jit

    def test_custom_vjp_boundary_counted(self):
        @jax.custom_vjp
        def f(x):
            return x * 2.0

        f.defvjp(lambda x: (x * 2.0, None), lambda _, g: (g * 2.0,))
        report = audit(lambda x: f(x).sum(), jnp.ones((3,)))
        assert report.custom_vjp_calls == 1

    def test_dot_records_accumulation_dtype(self):
        def good(a, b):
            return jnp.einsum('kp,p->k', a, b,
                              preferred_element_type=jnp.float32)

        report = audit(good, jnp.ones((4, 8), jnp.bfloat16),
                       jnp.ones((8,), jnp.bfloat16))
        (dot,) = report.dots
        assert dot.accum_dtype == 'float32' and dot.preferred

    def test_host_callback_flagged_in_jaxpr_and_stablehlo(self):
        def f(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct((3,), jnp.float32), x)
            return y.sum()

        report = audit(f, jnp.ones((3,)))
        sources = {t.source for t in report.host_transfers}
        assert 'jaxpr' in sources and 'stablehlo' in sources


# --------------------------------------------------------------- contracts
class TestContract:
    def test_clean_program_passes(self):
        c = Contract(name='clean', no_all_gather=True, no_host_transfer=True,
                     max_collectives={'psum': 1},
                     min_accum_dtype='float32')
        report = c.check_fn(_psum_fn(_mesh1()), jnp.ones((4, 8)))
        assert c.check(report) == []

    def test_no_all_gather_renders_the_offending_op(self):
        from repro.distributed.ctx import shard_map_unchecked
        mesh = _mesh1()
        gather = shard_map_unchecked(
            lambda x: jax.lax.all_gather(x, 'model', tiled=True),
            mesh, (P('model'),), P())
        report = audit(gather, jnp.ones((8,)))
        violations = Contract(no_all_gather=True).check(report)
        assert violations and violations[0].rule == 'no_all_gather'
        with pytest.raises(ContractViolation, match='all-gather'):
            Contract(name='gatherless', no_all_gather=True).enforce(report)

    def test_collective_count_bounds(self):
        fn = _psum_fn(_mesh1())

        def twice(x):
            return fn(x) + fn(x + 1.0)

        report = audit(twice, jnp.ones((4, 8)))
        assert Contract(exact_collectives={'psum': 2}).check(report) == []
        bad = Contract(exact_collectives={'psum': 1}).check(report)
        assert bad and 'exact 1' in bad[0].message
        assert Contract(max_collectives={'psum': 1}).check(report)
        assert Contract(min_collectives={'psum': 3}).check(report)
        # a kind that never appears violates min but satisfies max
        assert Contract(min_collectives={'all_gather': 1}).check(report)
        assert Contract(max_collectives={'all_gather': 0}).check(report) == []

    def test_min_accum_dtype_catches_bf16_accumulation(self):
        def bad(a, b):
            return jax.lax.dot(a, b)    # bf16 x bf16 -> bf16, no preferred

        report = audit(bad, jnp.ones((4, 8), jnp.bfloat16),
                       jnp.ones((8, 2), jnp.bfloat16))
        v = Contract(min_accum_dtype='float32').check(report)
        assert v and v[0].rule == 'min_accum_dtype'
        assert 'bfloat16' in v[0].message

    def test_min_reduction_dtype_catches_bf16_psum(self):
        from repro.distributed.ctx import shard_map_unchecked
        mesh = _mesh1()
        fn = shard_map_unchecked(
            lambda x: jax.lax.psum(x.sum(axis=-1), ('model',)),
            mesh, (P(None, 'model'),), P())
        report = audit(fn, jnp.ones((4, 8), jnp.bfloat16))
        v = Contract(min_reduction_dtype='float32').check(report)
        assert v and v[0].rule == 'min_reduction_dtype'

    def test_no_host_transfer_violation(self):
        def f(x):
            return jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct((3,),
                                                              jnp.float32), x)

        v = Contract(no_host_transfer=True).check(audit(f, jnp.ones((3,))))
        assert v and v[0].rule == 'no_host_transfer'

    def test_max_constant_bytes(self):
        baked = jnp.arange(4096, dtype=jnp.float32)

        def f(x):
            return x + baked

        report = audit(f, jnp.ones((4096,)))
        assert Contract(max_constant_bytes=100).check(report)
        assert Contract(max_constant_bytes=1 << 20).check(report) == []


# ----------------------------------------------- legacy-vs-contract parity
def test_contract_agrees_with_legacy_substring_method():
    """THE one sanctioned substring grep left: run the retired
    ``txt.count('all_reduce')`` methodology and the Contract on the same
    lowered flat_sharded apply and demand the same verdict — the port in
    test_block_apply.py / sharded_parity_check.py changed the mechanism,
    not the guarantee."""
    from repro.core import (FLAT_SHARDED_CONTRACT, FlatShardedBackend,
                            NystromIHVP, PyTreeIndexer, flatten_vec,
                            make_hvp, tree_random_like)

    idxr = PyTreeIndexer(PARAMS)
    B = jax.random.normal(jax.random.PRNGKey(7), (idxr.total, 16))
    Hm = B @ B.T / idxr.total + 0.5 * jnp.eye(idxr.total)
    hvp = make_hvp(lambda prm, hp, b: 0.5 * flatten_vec(prm) @ Hm
                   @ flatten_vec(prm), PARAMS, None, None)
    be = FlatShardedBackend(mesh=_mesh1(),
                            specs={'w': P('model'), 'm': P(None, 'model'),
                                   'b': P(), 's': P()})
    solver = NystromIHVP(k=8, rho=1e-2, backend=be, refine=0)
    state = solver.prepare(hvp, idxr, jax.random.PRNGKey(42))
    cols = [tree_random_like(k, PARAMS)
            for k in jax.random.split(jax.random.PRNGKey(1), 4)]
    Vm = jax.tree.map(lambda *ls: jnp.stack(ls, axis=-1), *cols)

    txt = jax.jit(solver.apply_matrix).lower(state, Vm).as_text()
    legacy_psums = txt.count('all_reduce')
    legacy_gathers = txt.count('all_gather')

    report = audit(solver.apply_matrix, state, Vm)
    assert report.count('psum') == legacy_psums == 1
    assert report.count('all_gather') == legacy_gathers == 0
    assert FLAT_SHARDED_CONTRACT.check(report) == []


# ------------------------------------------------------- wired-in contracts
def test_kernel_contract_holds_in_interpret_mode():
    """KERNEL_CONTRACT checks dots inside the pallas_call kernel jaxpr —
    bf16 slabs must upcast before the MXU dot."""
    from repro.kernels import ops

    C = jnp.asarray(np.random.default_rng(0).normal(size=(8, 256)),
                    jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        gram = functools.partial(ops.nystrom_gram, interpret=True)
        report = ops.KERNEL_CONTRACT.check_fn(gram, C.astype(dtype))
        assert report.dots, 'expected the kernel dot to be visible'

def test_bf16_sketch_contract_on_flat_backend():
    from repro.core import BF16_SKETCH_CONTRACT, get_backend

    be = get_backend('flat', sketch_dtype=jnp.bfloat16)
    C = {'w': jnp.ones((4, 8)), 'b': jnp.ones((4, 2))}
    op = be.prepare_operand(C)
    v = be.vec({'w': jnp.ones((8,)), 'b': jnp.ones((2,))})
    report = BF16_SKETCH_CONTRACT.check_fn(be.ctv, op, v)
    assert any(d.accum_dtype == 'float32' for d in report.dots)
    # the same contraction WITHOUT the f32 accumulation request violates
    bad = audit(lambda c, x: jnp.einsum('kp,p->k', c, x.astype(jnp.bfloat16)),
                op, v)
    assert BF16_SKETCH_CONTRACT.check(bad)


def test_serve_query_path_contract(monkeypatch):
    """InfluenceService.audit_query_path enforces SERVE_QUERY_CONTRACT on
    the real warm flush computation (apply_matrix + top-k scan)."""
    from repro.core import NystromIHVP, get_problem, train_influence_params
    from repro.serve.service import InfluenceService

    problem = get_problem('influence', d=8, width=8)
    params = train_influence_params(problem, train_steps=3)
    svc = InfluenceService(problem, NystromIHVP(k=4, rho=1e-2),
                           params=params, top_k=5, block_size=2)
    report = svc.audit_query_path()
    assert report.host_transfers == []
    assert all(d.accum_dtype in ('float32', 'float64') or
               d.accum_dtype not in ('bfloat16', 'float16')
               for d in report.dots)
