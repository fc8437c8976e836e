"""Seeded weights, made by the benchmark in the served dtype in one jitted call.

The tree has the structure the program's model takes (read from
``jax.eval_shape`` of its init, shapes only); every value comes from here, so
the reference, which reads the same tree, takes nothing the program made.
Each leaf is drawn from its own key, folded in from the seed and the leaf's
path, and its role sets its scale:

  * norm weights (``ln1``, ``ln2``, ``final_norm``, ``q_norm``, ``k_norm``):
    1 + 0.1 N(0, 1), so a norm that is skipped shows;
  * biases (``bq``, ``bk``, ``bv``): 0.1 N(0, 1), likewise;
  * the embedding: N(0, 1);
  * every projection (fan-in the second-to-last axis): N(0, 1 / fan-in).
"""

from __future__ import annotations

import zlib

import numpy as np

NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
BIASES = ("bq", "bk", "bv")


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (also beyond 32 bits)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(
        np.asarray(words, np.uint32), impl="threefry2x32"
    )


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(shapes, seed: int, device=None):
    """Weights of the tree ``shapes`` (ShapeDtypeStructs) from ``seed``."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        out = []
        for path, sd in flat:
            name = _leaf_name(path)
            k = jax.random.fold_in(
                key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF
            )
            z = jax.random.normal(k, sd.shape, jnp.float32)
            if name in NORMS:
                x = 1.0 + 0.1 * z
            elif name in BIASES:
                x = 0.1 * z
            elif name == "embed":
                x = z
            else:
                x = z / np.sqrt(sd.shape[-2])
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    placed = {} if device is None else {
        "out_shardings": jax.sharding.SingleDeviceSharding(device)
    }
    return jax.jit(init, **placed)(seed_key(seed))
