"""Operations a bilevel reweighting cycle requires, from the configuration's
shapes. Two operations per multiply-add; recomputation (remat) not counted.

Forward pass, per token, for the configuration's layers (`forward_flops`):

- llama: the q, k, v, o projections 2 d (2 H hd + 2 KV hd), the SwiGLU MLP
  6 d f, and attention 4 S H hd (QK^T and AV over the whole S x S block,
  as a dense causal attention computes it);
- rwkv6: the r, k, v, g, o projections 10 d^2, the decay's low-rank MLP
  4 d r (r = 64), the channel mix 4 d f + 2 d^2, and the wkv recurrence
  6 d hs (state update, bonus term and read-out, per head hs^2 each);
- the output head 2 d V over the vocabulary V the configuration names.

Norms, softmax, activations and the embedding lookup are left out: they are
a few percent, which is the margin of the check against XLA's own count
(`selftest/test_flops.py`).

With F the forward cost of one batch of T = batch x seq tokens:

- inner step, value and gradient: F + 2F = 3F (every matmul y = x W has
  two backward matmuls, dx = dy W^T and dW = x^T dy);
- one Hessian-vector product, forward over reverse (the sketch's columns):
  every forward matmul carries the tangent dy = dx W + x dW, two more
  matmuls, 3F; every backward matmul carries two more as well, 6F; in all
  9F;
- outer gradient grad_theta g on the outer batch: 3F;
- mixed term d/dphi <grad_theta f, u>: phi only weights each row's loss, so
  it needs each row's derivative along u, a forward pass with tangent u,
  F + 2F = 3F;
- the sketch's dense algebra: building the whitened factor B = C U and its
  Gram matrix, 4 k^2 p; the apply with one refinement sweep, four passes of
  (k, p) against a p-vector and back, 12 k p.

So a fresh outer step needs 6F + 9kF + 4k^2 p + 12 k p, an outer step that
reuses its sketch 6F + 12 k p, an inner step 3F.
"""
from __future__ import annotations


def recurrence_flops_per_token(c: dict) -> float:
    """The sequential part of the forward pass: rwkv6's wkv recurrence."""
    if c['family'] == 'rwkv6':
        return c['num_hidden_layers'] * 6 * c['hidden_size'] * c['head_size']
    return 0


def forward_flops_per_token(c: dict, seq: int) -> float:
    d, V, L = c['hidden_size'], c['vocab_size'], c['num_hidden_layers']
    if c['family'] == 'llama':
        H, KV, hd, f = (c['num_attention_heads'], c['num_key_value_heads'],
                        c['head_dim'], c['intermediate_size'])
        layer = 2 * d * (2 * H * hd + 2 * KV * hd) + 6 * d * f + 4 * seq * H * hd
    elif c['family'] == 'rwkv6':
        f = c['intermediate_size']
        layer = 10 * d * d + 4 * d * 64 + 4 * d * f + 2 * d * d
    else:
        raise ValueError(f"no FLOP count for family {c['family']!r}")
    return L * layer + 2 * d * V + recurrence_flops_per_token(c)


def cycle_flops(c: dict, traffic: dict, k: int, p: int) -> dict:
    """FLOPs of one refresh cycle: outer_every * sketch_refresh_every inner
    steps and sketch_refresh_every outer steps, the first of them fresh."""
    T = traffic['batch'] * traffic['seq']
    F = forward_flops_per_token(c, traffic['seq']) * T
    n_outer = traffic['sketch_refresh_every']
    inner = 3 * F
    reuse = 6 * F + 12 * k * p
    fresh = reuse + 9 * k * F + 4 * k * k * p
    total = traffic['outer_every'] * n_outer * inner + fresh + (n_outer - 1) * reuse
    return {'forward': F, 'inner': inner, 'outer_fresh': fresh,
            'outer_reuse': reuse, 'cycle': total}
