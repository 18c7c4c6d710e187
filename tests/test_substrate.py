"""Substrate tests: checkpointing (atomic/restart/corruption), data
determinism, loader prefetch, gradient compression, optimizers."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore, save
from repro.checkpoint.manager import latest_step
from repro.data.loader import Prefetcher, ShardedLoader
from repro.data.synthetic import LongTailDataset, TokenStream
from repro.distributed.compression import (ErrorFeedbackInt8,
                                           quantize_roundtrip)
from repro.optim import adafactor, adam, adamw, chain, clip_by_global_norm, sgd


# ------------------------------------------------------------- checkpointing
class TestCheckpoint:
    def _tree(self, seed=0):
        k = jax.random.PRNGKey(seed)
        return {'w': jax.random.normal(k, (8, 4)),
                'nested': {'b': jnp.arange(6, dtype=jnp.int32)},
                'scalar': jnp.float32(3.5)}

    def test_roundtrip(self, tmp_path):
        tree = self._tree()
        save(str(tmp_path), 7, tree, extra={'note': 'x'})
        out, manifest = restore(str(tmp_path), tree)
        assert manifest['step'] == 7
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(a, b)

    def test_latest_pointer_and_rotation(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        for s in (1, 2, 3):
            mgr.save(s, self._tree(s))
        assert mgr.latest_step() == 3
        kept = sorted(n for n in os.listdir(tmp_path) if n.startswith('step_'))
        assert len(kept) == 2                       # rotation

    def test_async_save_then_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        tree = self._tree(4)
        mgr.save(11, tree)
        mgr.wait()
        out, m = mgr.restore_latest(tree)
        assert m['step'] == 11

    def test_corruption_detected(self, tmp_path):
        tree = self._tree()
        d = save(str(tmp_path), 1, tree)
        path = os.path.join(d, 'arrays.npz')
        raw = bytearray(open(path, 'rb').read())
        raw[-9] ^= 0xFF                              # flip a payload byte
        open(path, 'wb').write(bytes(raw))
        with pytest.raises(Exception):
            restore(str(tmp_path), tree)

    def test_partial_write_never_becomes_latest(self, tmp_path):
        tree = self._tree()
        save(str(tmp_path), 1, tree)
        # simulate a crash mid-write of step 2: tmp dir exists, no rename
        os.makedirs(os.path.join(tmp_path, 'step_0000000002.tmp'))
        assert latest_step(str(tmp_path)) == 1
        CheckpointManager(str(tmp_path))             # GC cleans the .tmp
        assert not os.path.exists(
            os.path.join(tmp_path, 'step_0000000002.tmp'))

    def test_trainer_restart_resumes(self, tmp_path):
        """The fault-tolerance drill: train 60 steps, 'crash', relaunch with
        the same ckpt dir, verify it resumes past the checkpoint."""
        from repro.launch import train
        argv = ['--arch', 'yi_9b', '--reduced', '--batch', '2', '--seq', '32',
                '--outer-every', '1000', '--ckpt-every', '30',
                '--ckpt-dir', str(tmp_path), '--log-every', '0']
        train.main(argv + ['--steps', '35'])
        assert latest_step(str(tmp_path)) == 35
        run = train.main(argv + ['--steps', '45'])  # resumes at 35
        assert latest_step(str(tmp_path)) == 45
        assert len(run.losses) == 10 and np.isfinite(run.losses[-1])


# --------------------------------------------------------------------- data
class TestData:
    def test_token_stream_deterministic(self):
        s1 = TokenStream(vocab_size=512, seq_len=16)
        s2 = TokenStream(vocab_size=512, seq_len=16)
        b1, b2 = s1.batch(5, 4), s2.batch(5, 4)
        np.testing.assert_array_equal(b1['inputs'], b2['inputs'])

    def test_noisy_domains_are_harder(self):
        """Next-token predictability differs between clean/noisy domains —
        the signal the bilevel reweighting driver must find."""
        s = TokenStream(vocab_size=512, seq_len=64)
        b = s.batch(0, 256)
        inputs, labels, dom = (np.asarray(b['inputs']), np.asarray(b['labels']),
                               np.asarray(b['domain']))
        match = (s.next_tok[dom[:, None].repeat(64, 1),
                            inputs] == labels).mean(1)
        noisy = np.isin(dom, s.noisy_domains)
        assert match[~noisy].mean() > match[noisy].mean() + 0.3

    def test_longtail_profile(self):
        data = LongTailDataset(imbalance_factor=100)
        counts = np.bincount(np.asarray(data.y), minlength=10)
        assert counts[0] > 5 * counts[-1]            # heavy head (label noise
        # keeps tail counts nonzero)

    def test_loader_resume_state(self):
        stream = TokenStream(vocab_size=128, seq_len=8)
        l1 = ShardedLoader(lambda s: stream.batch(s, 2))
        next(l1)
        next(l1)
        st = l1.state_dict()
        l2 = ShardedLoader(lambda s: stream.batch(s, 2))
        l2.load_state_dict(st)
        np.testing.assert_array_equal(next(l1)['inputs'], next(l2)['inputs'])

    def test_prefetcher_order_and_errors(self):
        pf = Prefetcher(iter(range(5)), depth=2)
        assert list(pf) == list(range(5))

        def bad():
            yield 1
            raise RuntimeError('boom')

        pf = Prefetcher(bad(), depth=2)
        assert next(pf) == 1
        with pytest.raises(RuntimeError):
            next(pf)


# -------------------------------------------------------------- compression
class TestCompression:
    def test_quantize_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 5
        y = quantize_roundtrip(x)
        blk_max = float(jnp.abs(x).max())
        assert float(jnp.abs(x - y).max()) <= blk_max / 127 + 1e-6

    def test_error_feedback_accumulates(self):
        """With error feedback, the accumulated compressed sum tracks the
        accumulated true sum (the EF-SGD convergence ingredient)."""
        ef = ErrorFeedbackInt8()
        g = {'w': jnp.full((256,), 1e-3)}            # tiny: quantizes to ~0
        state = ef.init(g)
        total = jnp.zeros((256,))
        for _ in range(50):
            q, state = ef.update(g, state)
            total = total + q['w']
        np.testing.assert_allclose(total, 50e-3, rtol=0.15)

    def test_compressed_psum_matches_plain(self):
        """One contribution row per device; on the 1-device CPU mesh the
        quantized sum must round-trip the single contribution."""
        mesh = jax.make_mesh((1,), ('x',))
        from repro.distributed.compression import compressed_all_reduce
        x = jax.random.normal(jax.random.PRNGKey(1), (512,))
        out = jax.jit(lambda v: compressed_all_reduce(v, mesh, 'x'))(x[None])
        assert out.shape == x.shape
        np.testing.assert_allclose(out, x, atol=float(jnp.abs(x).max()) / 100)

    def test_compressed_all_reduce_sums_every_row(self):
        """More contribution rows than devices: every row must reach the
        sum (a 3-row stack on the 1-device mesh returns row0+row1+row2)."""
        mesh = jax.make_mesh((1,), ('x',))
        from repro.distributed.compression import compressed_all_reduce
        contribs = jnp.stack([jnp.full((64,), 1.0), jnp.full((64,), 2.0),
                              jnp.full((64,), 4.0)])
        out = compressed_all_reduce(contribs, mesh, 'x')
        np.testing.assert_allclose(out, 7.0, atol=0.1)


# ---------------------------------------------------------------- optimizers
@pytest.mark.parametrize('make', [lambda: sgd(0.1), lambda: adam(0.1),
                                  lambda: adamw(0.1, weight_decay=0.01),
                                  lambda: adafactor(0.1),
                                  lambda: chain(clip_by_global_norm(1.0),
                                                adam(0.1))])
def test_optimizers_minimize_quadratic(make):
    opt = make()
    params = {'w': jnp.ones((6, 3)) * 4.0, 'b': jnp.ones((3,))}
    st = opt.init(params)
    for i in range(300):
        g = jax.tree.map(lambda p: 2 * p, params)
        params, st = opt.apply(g, st, params, jnp.int32(i))
    norm = jnp.sqrt(sum(jnp.sum(p * p) for p in jax.tree.leaves(params)))
    assert norm < 0.2
