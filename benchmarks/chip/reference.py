"""Plain reference of what the timed trainer computes, from the configuration
file alone: the model's forward pass and weighted loss, the inner optimizer's
step, the outer optimizer's step and the hypergradient.

It imports nothing of the program. It reads the weights the benchmark made
from the seed (`weights.py`) in the trainer's tree layout (leaf names such as
`blocks/slot0/mixer/wq`, stacked layers on a leading axis), and the rows the
traffic's generator makes (`tokens.py`).

Precision: `Prec('f32')` computes in float32 with every matmul at HIGHEST, the
reference. `Prec('fp8')` rounds every matmul operand to float8_e4m3fn with a
per-tensor scale (derivatives pass through unrounded): the control, one step
below the bfloat16 matmuls the configurations state.

The hypergradient is that of the implicit function theorem,
    hg = -(d/dphi grad_theta f(theta, phi))^T u,   u = (H_k + rho I)^-1 grad_theta g,
with H_k the rank-k Nystrom approximation of the inner loss's Hessian from
k columns drawn uniformly without replacement (`columns`, `ihvp`): the
paper's Eq. 4-6.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
RECURRENCE_CHUNK = 16


class Prec:
    def __init__(self, name: str):
        if name not in ('f32', 'fp8'):
            raise ValueError(name)
        self.name = name

    def q(self, x):
        """float8_e4m3fn with one scale per tensor, its largest magnitude
        mapped to the format's largest finite value (448). Derivatives pass
        through unrounded, so HVPs and the backward pass stay finite."""
        if self.name == 'fp8':
            s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
            r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return x + jax.lax.stop_gradient(r - x)
        return x

    def mm(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary embedding of (B, S, H, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _llama_layer(c, P, lp, x):
    B, S, _ = x.shape
    H, KV, hd = c['num_attention_heads'], c['num_key_value_heads'], c['head_dim']
    eps = c['rms_norm_eps']
    a, f = lp['mixer'], lp['ffn']
    h = _rmsnorm(x, lp['ln1']['scale'], eps)
    q = P.mm('bsd,de->bse', h, a['wq']).reshape(B, S, H, hd)
    k = P.mm('bsd,de->bse', h, a['wk']).reshape(B, S, KV, hd)
    v = P.mm('bsd,de->bse', h, a['wv']).reshape(B, S, KV, hd)
    q, k = _rope(q, c['rope_theta']), _rope(k, c['rope_theta'])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    logits = P.mm('bshd,bthd->bhst', q, k) * hd ** -0.5
    logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -jnp.inf)
    o = P.mm('bhst,bthd->bshd', jax.nn.softmax(logits, -1), v).reshape(B, S, H * hd)
    x = x + P.mm('bse,ed->bsd', o, a['wo'])
    h = _rmsnorm(x, lp['ln2']['scale'], eps)
    g = jax.nn.silu(P.mm('bsd,df->bsf', h, f['w1'])) * P.mm('bsd,df->bsf', h, f['w3'])
    return x + P.mm('bsf,fd->bsd', g, f['w2'])


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _rwkv6_layer(c, P, lp, x):
    """Time mix (data-dependent decay, wkv recurrence, per-head group norm)
    and channel mix, each after an RMSNorm, each added to the residual."""
    B, S, d = x.shape
    hs = c['head_size']
    H = d // hs
    eps = c['layer_norm_epsilon']
    m = lp['mixer']
    h = _rmsnorm(x, lp['ln1']['scale'], eps)
    hx = _shift(h) - h
    mix = [h + hx * m['mu'][i] for i in range(5)]
    r = P.mm('bsd,de->bse', mix[0], m['wr']).reshape(B, S, H, hs)
    k = P.mm('bsd,de->bse', mix[1], m['wk']).reshape(B, S, H, hs)
    v = P.mm('bsd,de->bse', mix[2], m['wv']).reshape(B, S, H, hs)
    g = jax.nn.silu(P.mm('bsd,de->bse', mix[3], m['wg']))
    w_raw = m['w0'] + P.mm('bsr,rd->bsd', jnp.tanh(P.mm('bsd,dr->bsr', mix[4],
                                                        m['w_lora_a'])), m['w_lora_b'])
    w = jnp.exp(-jnp.exp(w_raw)).reshape(B, S, H, hs)
    u = jnp.exp(m['bonus'])                                   # (H, hs)

    def step(state, t):                                       # state (B,H,k,v)
        r_t, k_t, v_t, w_t = t
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum('bhk,bhkv->bhv', r_t, state + u[None, :, :, None] * kv,
                       precision=HIGHEST)
        return state * w_t[..., :, None] + kv, y

    # the recurrence in chunks of RECURRENCE_CHUNK steps, each chunk's
    # states recomputed in the backward pass, so that the full-width HVPs fit
    n = RECURRENCE_CHUNK if S % RECURRENCE_CHUNK == 0 else S
    seq = tuple(a.transpose(1, 0, 2, 3).reshape(S // n, n, B, H, hs) for a in (r, k, v, w))
    chunk = jax.checkpoint(lambda st, xs: jax.lax.scan(step, st, xs))
    _, y = jax.lax.scan(chunk, jnp.zeros((B, H, hs, hs), jnp.float32), seq)
    y = y.reshape(S, B, H, hs).transpose(1, 0, 2, 3)          # (B,S,H,hs)
    mean = y.mean(-1, keepdims=True)
    var = ((y - mean) ** 2).mean(-1, keepdims=True)
    y = (y - mean) * jax.lax.rsqrt(var + eps) * m['ln_scale']
    x = x + P.mm('bsd,de->bse', y.reshape(B, S, d) * g, m['wo'])
    h = _rmsnorm(x, lp['ln2']['scale'], eps)
    hx = _shift(h) - h
    xk, xr = h + hx * m['mu_cm'][0], h + hx * m['mu_cm'][1]
    kk = jnp.square(jax.nn.relu(P.mm('bsd,df->bsf', xk, m['ck'])))
    rr = jax.nn.sigmoid(P.mm('bsd,de->bse', xr, m['cr']))
    return x + rr * P.mm('bsf,fd->bsd', kk, m['cv'])


LAYERS = {'llama': _llama_layer, 'rwkv6': _rwkv6_layer}


def logits(c, P, params, tokens):
    x = params['embed']['table'][tokens]
    layer = LAYERS[c['family']]
    blocks = params['blocks']['slot0']
    for i in range(c['num_hidden_layers']):
        x = layer(c, P, jax.tree.map(lambda a: a[i], blocks), x)
    eps = c.get('rms_norm_eps', c.get('layer_norm_epsilon'))
    x = _rmsnorm(x, params['final_norm']['scale'], eps)
    out = P.mm('bsd,vd->bsv', x, params['unembed']['table'])
    valid = jnp.arange(out.shape[-1]) < c['vocab_size']
    return jnp.where(valid, out, -jnp.inf)


def loss(c, P, params, phi, batch, half=False):
    """Token-mean cross-entropy; with `phi`, each row weighted by
    n_domain_logits * softmax(phi)[its domain]. `half` keeps only the first
    half of the rows (a planted fault)."""
    if half:
        batch = jax.tree.map(lambda a: a[:a.shape[0] // 2], batch)
    z = logits(c, P, params, batch['inputs'])
    lse = jax.nn.logsumexp(z, -1)
    ll = jnp.take_along_axis(z, batch['labels'][..., None], -1)[..., 0]
    tok = lse - ll
    if phi is None:
        w = jnp.ones(tok.shape[:1], jnp.float32)
    else:
        w = (jax.nn.softmax(phi) * phi.shape[0])[batch['domain']]
    mask = jnp.broadcast_to(w[:, None], tok.shape)
    return (tok * mask).sum() / mask.sum()


def adam_update(o, g, m, v, p, step, weight_decay=0.0):
    """Adam(W) with bias correction at count step + 1; returns (p, m, v)."""
    count = step.astype(jnp.float32) + 1.0
    m = o['b1'] * m + (1 - o['b1']) * g
    v = o['b2'] * v + (1 - o['b2']) * jnp.square(g)
    bc1, bc2 = 1 - o['b1'] ** count, 1 - o['b2'] ** count
    upd = -o['lr'] * (m / bc1) / (jnp.sqrt(v / bc2) + o['eps'])
    return p + upd - o['lr'] * weight_decay * p, m, v


def _flat(tree):
    return jnp.concatenate([x.ravel() for x in jax.tree.leaves(tree)])


def _unflat(vec, like):
    leaves, treedef = jax.tree.flatten(like)
    out, at = [], 0
    for x in leaves:
        out.append(vec[at:at + x.size].reshape(x.shape).astype(x.dtype))
        at += x.size
    return treedef.unflatten(out)


def make_programs(c, trainer, prec: str, half: bool = False):
    """Jitted pieces of the reference, in a dict:

    inner_step(params, m, v, phi, batch, step) -> (params, m, v, loss, clipped grad)
    outer_grad(params, batch) -> grad_theta g, the unweighted loss's gradient
    columns(params, phi, batch, key) -> (C, H_KK): the rank-k Nystrom sketch,
        C = H[:, K] (k, p) with K drawn uniformly without replacement from
        the flat parameter index by `jax.random.choice(key, p, (k,))`
    mixed(params, phi, batch, u) -> -(d/dphi grad_theta f)^T u
    outer_step(phi, m, v, hg, step) -> (phi, m, v)
    """
    P = Prec(prec)
    io, oo = trainer['inner_optimizer'], trainer['outer_optimizer']
    k = trainer['sketch_rank']
    f = functools.partial(loss, c, P)

    @jax.jit
    def inner_step(params, m, v, phi, batch, step):
        val, g = jax.value_and_grad(lambda th: f(th, phi, batch, half))(params)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, io['clip_global_norm']
                                                   / (norm + 1e-12)), g)
        out = jax.tree.map(lambda gg, mm, vv, pp: adam_update(
            io, gg, mm, vv, pp, step, io['weight_decay']), g, m, v, params)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), val, g

    @jax.jit
    def outer_grad(params, batch):
        return jax.grad(lambda th: f(th, None, batch))(params)

    @jax.jit
    def columns(params, phi, batch, key):
        p = sum(x.size for x in jax.tree.leaves(params))
        idx = jax.random.choice(key, p, (k,), replace=False).astype(jnp.int32)
        grad = jax.grad(lambda th: f(th, phi, batch, half))

        def col(j):
            e = _unflat(jnp.zeros((p,), jnp.float32).at[j].set(1.0), params)
            return _flat(jax.jvp(grad, (params,), (e,))[1])
        C = jax.lax.map(col, idx)
        return C, C[:, idx]

    @jax.jit
    def mixed(params, phi, batch, u):
        def inner_dot(ph):
            gi = jax.grad(lambda th: f(th, ph, batch, half))(params)
            return sum(jnp.vdot(a, b, precision=HIGHEST)
                       for a, b in zip(jax.tree.leaves(gi), jax.tree.leaves(u)))
        return -jax.grad(inner_dot)(phi)

    @jax.jit
    def outer_step(phi, m, v, hg, step):
        return adam_update(oo, hg, m, v, phi, step)

    return {'inner_step': inner_step, 'outer_grad': outer_grad, 'columns': columns,
            'mixed': mixed, 'outer_step': outer_step}


@jax.jit
def _c_v(C, v):
    return jnp.einsum('kp,p->k', C, v, precision=HIGHEST), jnp.einsum(
        'kp,jp->kj', C, C, precision=HIGHEST)


@jax.jit
def _ct_w(C, w):
    return jnp.einsum('kp,k->p', C, w, precision=HIGHEST)


def ihvp(sketch, g, rho: float):
    """u = (H_k + rho I)^-1 g for the Nystrom approximation
    H_k = C^T H_KK^+ C of a sketch (C, H_KK), or g / rho without one.

    H_KK^+ keeps the eigenvalues above 1e-7 x k x the largest magnitude: the
    pseudo-inverse drops directions of non-positive or vanishing curvature.
    With W = U diag(lam^-1/2) over the kept ones and B = C^T W, the Woodbury
    identity gives u = (g - B (B^T B + rho I)^-1 B^T g) / rho; the k x k
    algebra is done in float64.
    """
    import numpy as np
    if sketch is None:
        return jax.tree.map(lambda x: x / rho, g)
    C, hkk = sketch
    v = _flat(g)
    cv, gram = (np.asarray(x, np.float64) for x in _c_v(C, v))
    hkk = np.asarray(hkk, np.float64)
    lam, U = np.linalg.eigh(0.5 * (hkk + hkk.T))
    keep = lam > 1e-7 * hkk.shape[0] * (np.abs(lam).max() + 1e-30)
    W = U[:, keep] / np.sqrt(lam[keep])
    y = np.linalg.solve(W.T @ gram @ W + rho * np.eye(W.shape[1]), W.T @ cv)
    u = (v - _ct_w(C, jnp.asarray(W @ y, jnp.float32))) / rho
    return _unflat(u, g)
