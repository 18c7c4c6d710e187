"""Dropless sort-based Mixture-of-Experts with `lax.ragged_dot`.

Design (DESIGN.md §4): tokens are argsorted by assigned expert and hit their
expert's weights through `ragged_dot`, so compiled FLOPs equal the *active*
FLOPs (6·N_active·D shows up cleanly in the MODEL_FLOPS/HLO_FLOPs roofline
ratio — no capacity-factor waste, no dropped tokens). Expert weights are
tensor-parallel on d_ff over the ``model`` axis, so there is **no all-to-all**:
the only collective is the usual row-parallel psum on the second matmul,
identical to a dense FFN. (An EP/all-to-all layout is a recorded hillclimb
alternative for decode, where per-device token counts are tiny.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _act, cdtype, dense_init, pdtype


def init_moe(cfg: ModelConfig, rng) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(rng, 5)
    dt = pdtype(cfg)
    p = {'router': dense_init(ks[0], (d, E), dt, scale=d ** -0.5),
         'w1': dense_init(ks[1], (E, d, f), dt),
         'w3': dense_init(ks[2], (E, d, f), dt),
         'w2': dense_init(ks[3], (E, f, d), dt)}
    if cfg.shared_expert:
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p['shared'] = {'w1': dense_init(k1, (d, f), dt),
                       'w3': dense_init(k2, (d, f), dt),
                       'w2': dense_init(k3, (f, d), dt)}
    return p


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rdot(x, w, group_sizes, dx_reduce=(), dw_reduce=()):
    """ragged_dot with a memory-sane VJP.

    jax's built-in ragged_dot transpose materializes a dense (E, N, d)
    one-hot/select tensor (measured 172 GB × several on llama4 train); both
    cotangents are themselves ragged contractions:
      dx = ragged_dot(dy, wᵀ)            (same grouping)
      dw = ragged_dot_general(x, dy)     (ragged dim contracting → (E, d, f))

    ``dx_reduce``/``dw_reduce``: mesh axes to psum the cotangents over when
    running inside shard_map — a cotangent must match its primal's varying
    axes (x is model-invariant ⇒ dx psums over 'model'; w is batch-invariant
    ⇒ dw psums over the batch axes). Empty tuples outside shard_map.
    """
    return jax.lax.ragged_dot(x, w, group_sizes)


def _rdot_fwd(x, w, group_sizes, dx_reduce=(), dw_reduce=()):
    return jax.lax.ragged_dot(x, w, group_sizes), (x, w, group_sizes)


def _rdot_bwd(dx_reduce, dw_reduce, res, dy):
    x, w, group_sizes = res
    dx = jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), group_sizes)
    rdn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[])
    dw = jax.lax.ragged_dot_general(x.astype(jnp.float32),
                                    dy.astype(jnp.float32), group_sizes, rdn)
    if dx_reduce:
        dx = jax.lax.psum(dx, dx_reduce)
    if dw_reduce:
        dw = jax.lax.psum(dw, dw_reduce)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_rdot.defvjp(_rdot_fwd, _rdot_bwd)


def _moe_local(params, xt, cfg: ModelConfig, axis_names=(), impl='ragged'):
    """Per-shard MoE body. xt: (N_local, d) with the *full* d; expert weights
    are the local d_ff slice. ``axis_names``: (model_axes, batch_axes) when
    running under shard_map — the w2 partial is psum'd over model, the aux
    statistics pmean'd over batch.
    """
    ct = cdtype(cfg)
    N, d = xt.shape
    E, k = cfg.n_experts, cfg.top_k

    router_logits = (xt.astype(jnp.float32)
                     @ params['router'].astype(jnp.float32))        # (N, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)                      # (N, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)       # renormalize

    # ---- load-balance aux (Switch-style): E · <fraction, prob> ----
    frac = jnp.mean(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32),
                    axis=(0, 1))
    mean_probs = probs.mean(0)
    model_axes, batch_axes = (axis_names or ((), ()))
    if batch_axes:
        frac = jax.lax.pmean(frac, batch_axes)
        mean_probs = jax.lax.pmean(mean_probs, batch_axes)
    aux = E * jnp.sum(frac * mean_probs) * cfg.router_aux_coef

    if impl == 'ragged':
        # -- dropless: sort token-replicas by expert id (local, no comms) --
        flat_expert = expert_idx.reshape(N * k)                     # (Nk,)
        order = jnp.argsort(flat_expert, stable=True)
        inv_order = jnp.argsort(order)
        token_of = order // k                                       # source token
        xs = jnp.take(xt, token_of, axis=0).astype(ct)              # (Nk, d)
        group_sizes = jnp.bincount(flat_expert, length=E)
        # xs is model-invariant (dx psums over model); weights batch-
        # invariant (dw psums over batch); h varies on both (no reduce).
        h = _rdot(xs, params['w1'].astype(ct), group_sizes,
                  model_axes, batch_axes)
        g = _rdot(xs, params['w3'].astype(ct), group_sizes,
                  model_axes, batch_axes)
        h = _act(cfg.act)(h) * g
        out_sorted = _rdot(h, params['w2'].astype(ct), group_sizes,
                           (), batch_axes)
        out = jnp.take(out_sorted, inv_order, axis=0).reshape(N, k, d)
        out = jnp.einsum('nkd,nk->nd', out.astype(jnp.float32), gate)
    else:
        # -- fixed-capacity dispatch (GShard/Switch): scatter → batched
        # einsum → gather. Pure dense ops ⇒ partitions on every backend
        # (ragged_dot's non-TPU lowering materializes dense (E,N,d) masks —
        # measured 730 GB/chip on llama4 before this). cap·E ≈ 1.25·N·k
        # slots; overflow tokens fall back to their gate-weighted residual.
        Nk = N * k
        cap = Nk if Nk <= 8 * E else min(
            Nk, max(8, int(1.25 * Nk / E + 7) // 8 * 8))
        flat_expert = expert_idx.reshape(Nk)
        onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)    # (Nk, E)
        pos = jnp.cumsum(onehot, axis=0) - onehot                   # pre-count
        slot = jnp.take_along_axis(pos, flat_expert[:, None], 1)[:, 0]
        keep = (slot < cap).astype(jnp.float32)
        token_of = jnp.arange(Nk) // k
        buf = jnp.zeros((E, cap, d), ct).at[flat_expert, slot].add(
            jnp.take(xt, token_of, axis=0).astype(ct)
            * keep[:, None].astype(ct))
        h = jnp.einsum('ecd,edf->ecf', buf, params['w1'].astype(ct))
        g = jnp.einsum('ecd,edf->ecf', buf, params['w3'].astype(ct))
        h = _act(cfg.act)(h) * g
        y = jnp.einsum('ecf,efd->ecd', h, params['w2'].astype(ct))
        picked = y[flat_expert, slot] * keep[:, None]               # (Nk, d)
        out = jnp.einsum('nkd,nk->nd', picked.reshape(N, k, d)
                         .astype(jnp.float32), gate)

    if cfg.shared_expert:
        sp = params['shared']
        hs = _act(cfg.act)(xt.astype(ct) @ sp['w1'].astype(ct)) \
            * (xt.astype(ct) @ sp['w3'].astype(ct))
        out = out + (hs @ sp['w2'].astype(ct)).astype(jnp.float32)

    if model_axes:
        # row-parallel second matmul: one activation psum, same as dense FFN
        out = jax.lax.psum(out, model_axes)
    return out.astype(ct), aux


def moe_ffn(params, x, cfg: ModelConfig):
    """x: (B, S, d) → (B, S, d), plus router load-balancing aux loss.

    Distribution: GSPMD cannot partition `ragged_dot` (it replicates the
    (E, d, d_ff) expert weights — measured 2 TB/chip on llama4 before this),
    so under a mesh the expert compute runs inside an explicit `shard_map`:
    tokens stay on their (pod, data) shard (dispatch/sort is shard-local —
    zero collective), expert weights are TP-split on d_ff over 'model', and
    the only communication is the dense-FFN-equivalent psum of the output.
    """
    from repro.distributed.ctx import current_mesh
    B, S, d = x.shape
    N = B * S
    xt = x.reshape(N, d)
    mesh = current_mesh()
    if mesh is None:
        out, aux = _moe_local(params, xt, cfg)
        return out.reshape(B, S, d), aux

    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ('pod', 'data') if a in mesh.axis_names)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if N % n_batch != 0:
        batch_axes = ()
    model_axes = ('model',) if ('model' in mesh.axis_names
                                and cfg.d_ff % mesh.shape['model'] == 0) else ()
    tok_spec = P(batch_axes if batch_axes else None, None)
    w_col = P(None, None, model_axes[0]) if model_axes else P(None, None, None)
    w_row = P(None, model_axes[0], None) if model_axes else P(None, None, None)
    pspec = {'router': P(None, None), 'w1': w_col, 'w3': w_col, 'w2': w_row}
    if cfg.shared_expert:
        m0 = model_axes[0] if model_axes else None
        pspec['shared'] = {'w1': P(None, m0), 'w3': P(None, m0),
                           'w2': P(m0, None)}

    out, aux = jax.shard_map(
        lambda p_, x_: _moe_local(p_, x_, cfg, (model_axes, batch_axes),
                                  impl='capacity'),
        mesh=mesh,
        in_specs=(pspec, tok_spec),
        out_specs=(tok_spec, P()),
    )(params, xt)
    return out.reshape(B, S, d), aux
