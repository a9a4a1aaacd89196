"""Cluster-scale graph analytics — the paper's workload on a device mesh.

Runs edge-partitioned PageRank via shard_map on every local device (on
this container: 8 XLA host-platform devices), shows that LOrder
concentrates the *useful* share of the all-gather payload into a hot
prefix — the cluster-level analogue of the paper's cache-line locality —
and validates against the single-device kernel.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_graph.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax
import numpy as np


def hot_prefix_payload(g, perm, num_shards: int, prefix_frac: float = 0.1):
    """Share of cross-shard property reads served by the hottest
    ``prefix_frac`` of vertex ids (what a prefix-cached all-gather saves)."""
    gp = g.apply_permutation(perm) if perm is not None else g
    reads = gp.transpose.indices            # property reads, pull mode
    n = gp.num_vertices
    per = -(-n // num_shards)
    dst = gp.transpose.edge_src
    cross = (reads // per) != (dst // per)  # read crosses a shard boundary
    hot = reads < int(n * prefix_frac)
    return float((cross & hot).sum() / max(cross.sum(), 1))


def main():
    from repro.algos.graph_arrays import to_device
    from repro.algos.kernels import pagerank
    from repro.core.dist import make_distributed_pagerank, vertex_mesh
    from repro.core.generators import powerlaw_community
    from repro.core.lorder import lorder

    n_dev = len(jax.devices())
    mesh = vertex_mesh(n_dev)
    print(f"[mesh] {n_dev} devices on axis 'data'")

    g = powerlaw_community(40_000, avg_degree=12, seed=13)
    print(f"[graph] V={g.num_vertices:,} E={g.num_edges:,}")

    print("[lorder] reordering...")
    perm = np.asarray(lorder(g))
    gp = g.apply_permutation(perm)

    for name, graph, p in (("original", g, None), ("lorder", gp, perm)):
        share = hot_prefix_payload(g, p, n_dev)
        print(f"   {name:9s}: hottest 10% of ids serve "
              f"{100 * share:.1f}% of cross-shard property reads")

    print("[dist-pr] running edge-partitioned PageRank on the mesh...")
    run, _ = make_distributed_pagerank(gp, mesh, axis="data", num_iters=20)
    r_dist = np.asarray(run())
    r_single = np.asarray(pagerank(to_device(gp), num_iters=20))
    err = np.abs(r_dist - r_single).max()
    print(f"[dist-pr] max |dist - single| = {err:.2e} "
          f"({'OK' if err < 1e-5 else 'MISMATCH'})")


if __name__ == "__main__":
    main()
