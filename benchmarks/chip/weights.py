"""Weights and resumed training state from the seed, on the device.

`init_rules` come from the configuration file: a list of
`[regex, kind, *args]`, matched in order against each leaf's path
(`blocks/slot0/mixer/wq`); the first match sets the leaf. Kinds:

- `const c`: every entry c.
- `normal std`: normal with that standard deviation.
- `uniform lo hi`: uniform on [lo, hi).
- `fan_in g`: truncated normal (±3 std), std = g / sqrt(shape[-2]), the
  usual fan-in scale of a weight matrix; a leading axis of stacked layers
  does not count.

All leaves are drawn in one jitted call, in the dtype of the template leaf.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_path(path) -> str:
    return '/'.join(str(getattr(p, 'key', getattr(p, 'idx', p))) for p in path)


def _draw(key, rule, shape, dtype):
    kind, args = rule[1], rule[2:]
    if kind == 'const':
        x = jnp.full(shape, args[0], jnp.float32)
    elif kind == 'normal':
        x = args[0] * jax.random.normal(key, shape, jnp.float32)
    elif kind == 'uniform':
        x = jax.random.uniform(key, shape, jnp.float32, args[0], args[1])
    elif kind == 'fan_in':
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        x = (args[0] * fan_in ** -0.5) * jax.random.truncated_normal(
            key, -3.0, 3.0, shape, jnp.float32)
    else:
        raise ValueError(f'unknown init kind {kind!r}')
    return x.astype(dtype)


def make_params(init_rules, shapes, seed: int):
    """The parameter tree with the structure and dtypes of `shapes`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [leaf_path(p) for p, _ in flat]
    rules = []
    for path in paths:
        rule = next((r for r in init_rules if re.search(r[0], path)), None)
        if rule is None:
            raise ValueError(f'no init rule matches leaf {path!r}')
        rules.append(rule)

    @jax.jit
    def draw(key):
        leaves = [_draw(jax.random.fold_in(key, i), rule, s.shape, s.dtype)
                  for i, (rule, (_, s)) in enumerate(zip(rules, flat))]
        return treedef.unflatten(leaves)

    return draw(seed_key(seed))


@jax.jit
def zeros_like_tree(tree):
    return jax.tree.map(jnp.zeros_like, tree)
