"""Weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: the program under test and the plain
reference are both handed values drawn here, each leaf from a key
folded from the run's seed and the leaf's name, so the two sides agree
without either reading the other's arrays.  A spec is
``{name: {"shape": [...], "init": "normal" | "around_one" | "ones" |
"zeros", "std": float}}`` (``around_one`` is 1 + std x a normal draw).
"""
import re
import zlib

_LAYER = re.compile(r"^layer(\d+)_(.+)$")


def seed_key(seed):
    """A PRNG key for any non-negative whole number (the driver's seeds
    pass 2**31, which a 32-bit key constructor would refuse)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, name, spec, dtype, round_to):
    import jax
    import jax.numpy as jnp

    shape = tuple(spec["shape"])
    kind = spec.get("init", "normal")
    if kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif kind in ("normal", "around_one"):
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        x = spec.get("std", 0.02) * jax.random.normal(k, shape, jnp.float32)
        if kind == "around_one":
            x = 1.0 + x
    else:
        raise ValueError(f"{name}: unknown init {kind!r}")
    if round_to is not None:
        x = x.astype(round_to)
    return x.astype(dtype)


def make(specs, seed, dtype, round_to=None, stack_layers=0):
    """Every leaf of ``specs`` as ``dtype``, in one program.

    ``round_to``: round the drawn values through this type first (the
    reference of a model served in bfloat16 computes in float32 on the
    values that are served).  ``stack_layers`` > 0: leaves named
    ``layer<i>_<x>`` come back stacked as ``layers[<x>]`` with a leading
    axis of that many layers, for a reference that scans over depth."""
    import jax
    import jax.numpy as jnp

    names = sorted(specs)

    def build(key):
        flat = {n: _leaf(key, n, specs[n], dtype, round_to) for n in names}
        if not stack_layers:
            return flat
        out, per_layer = {}, {}
        for n, v in flat.items():
            m = _LAYER.match(n)
            if m:
                per_layer.setdefault(m.group(2), {})[int(m.group(1))] = v
            else:
                out[n] = v
        out["layers"] = {
            k: jnp.stack([v[i] for i in range(stack_layers)])
            for k, v in per_layer.items()}
        return out

    return jax.jit(build)(seed_key(seed))
