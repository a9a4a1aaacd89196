"""Graph500 Kronecker (R-MAT) edge generator, made on the device from a seed.

The same recursive quadrant choice as the Graph500 reference generator:
each of ``scale`` bits of an edge's endpoints picks one of four quadrants
with probabilities a, b, c and 1 - a - b - c, and vertex labels are then
permuted so that generation order carries no information. Self-loops and
duplicate edges are kept, as Graph500's edge list keeps them. The graph
is undirected, as Graph500's Kernel 1 builds it: each of the
``edge_factor * 2**scale`` generated edges is served as two arcs, u -> v
and v -> u, so the edge list has ``2 * edge_factor * 2**scale`` arcs.

All arcs come from one jitted call on the device, so a run's set-up pays
well under a second for them at scale 20 where a host loop takes tens of
seconds.
"""
from __future__ import annotations

import functools

import numpy as np


def sizes(config: dict) -> tuple[int, int]:
    """(vertices, arcs) of the generated edge list, both directions."""
    n = 1 << int(config["scale"])
    return n, 2 * n * int(config["edge_factor"])


def _key(seed: int):
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _program(scale: int, num_edges: int, a: float, b: float, c: float):
    import jax
    import jax.numpy as jnp
    ab, abc = a + b, a + b + c

    @jax.jit
    def run(key):
        k_bits, k_perm = jax.random.split(key)

        def bit(i, state):
            src, dst = state
            r = jax.random.uniform(jax.random.fold_in(k_bits, i),
                                   (num_edges,))
            go_right = ((r >= a) & (r < ab)) | (r >= abc)
            go_down = r >= ab
            return ((src << 1) | go_down.astype(jnp.int32),
                    (dst << 1) | go_right.astype(jnp.int32))

        zero = jnp.zeros((num_edges,), jnp.int32)
        src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
        relabel = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
        src, dst = relabel[src], relabel[dst]
        return jnp.concatenate([src, dst]), jnp.concatenate([dst, src])

    return run


def generate(config: dict, seed: int) -> tuple[int, np.ndarray, np.ndarray]:
    """``(num_vertices, src, dst)`` with int64 host edge arrays."""
    n, m = sizes(config)
    run = _program(int(config["scale"]), m // 2, float(config["a"]),
                   float(config["b"]), float(config["c"]))
    src, dst = run(_key(seed))
    return n, np.asarray(src).astype(np.int64), np.asarray(dst).astype(np.int64)
