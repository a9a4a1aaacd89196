"""Chip smoke: serve the engine's main path on a TPU and check every answer.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded path, on a 4-chip host

One chip: a Graph500 Kronecker graph (RMAT a=.57, b=c=.19, edge factor
16) at scale 20 and a SIFT-width (dim 128) clustered vector corpus with
an NSW search graph are registered through ``EngineSession.register``,
which probes each graph and applies the reorder its policy picks. Traffic
goes through ``enqueue`` -> ``QueryFuture.result()``: bursts of 32 BFS
and 32 SSSP sources that coalesce into shared launches, CC, two PR
requests that deduplicate into one run, BC over 4 sources, and 64 knn
queries, each mix served twice: cold, then warm with the result cache
emptied. Answers are checked against the plain host references in
``core/baselines.py``, and knn recall against exact search
(``search/knn_graph.knn_brute_force``) against a floor.

``--four-chips`` runs only the sharded path: the same kernels placed on
``ShardedBackend`` across the four chips (forced by a device budget of
one byte) and compared with the same kernels on one of the four.

It is one process with no children, so it holds the chip from start to
end. It exits non-zero, before any work and with no result line, when
JAX reports no TPU. Any failed check, exception, failed future or
profiler error also exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SCALE = 20               # Graph500 scale: V = 2**20, E = 16 * V
# Cut from scale 22 (Graph500's smallest "toy" class): served once cold
# and once warm there, a 32-source SSSP burst ran 202 s and BFS 80 s per
# launch on one v5e, which puts the whole smoke past its 20-minute limit.
SCALE_CUT = "22 -> 20: scale-22 kernel walls exceed the run's time limit"
BURST = 32               # BFS and SSSP sources per burst
BC_SOURCES = 4
KNN_CORPUS = 20_000      # vectors; the NSW build is host Python
KNN_DIM = 128            # SIFT's width
KNN_GRID = 64            # integer-valued coordinates, as SIFT's are
KNN_K_OUT = 16
KNN_QUERIES = 64
SEED = 0
# recall@10 against exact search that a served knn burst must reach. On
# this corpus a query's neighbors other than its own source point are
# near-ties inside one isotropic blob, which gives the beam no gradient
# to follow: at 20k vectors recall@10 is 0.38 at the default budget, 0.40
# with the previous NSW builder, 0.22 on the exact 16-NN graph and 0.69
# at beam 128 / 512 steps (host mirror, CPU); at 2k it is 0.98. The floors
# catch a broken graph or search, not a slow one.
KNN_RECALL_FLOOR, KNN_SMALL_RECALL_FLOOR = 0.3, 0.9   # 20k, <= 2k vectors

# the sharded path only needs shapes that exercise the mesh: a smaller
# deployment keeps the host work off four chips' clock
FOUR_CHIP_SCALE = 16
FOUR_CHIP_CORPUS = 2_000


class SmokeFailure(Exception):
    """A served answer disagreed with its reference."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  check ok: {what}")


def require_tpu(count: int):
    """The chip, or exit: there is no CPU branch."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX reports platform "
                 f"{devices[0].platform!r} ({len(devices)} device(s)); "
                 f"run it on the machine with the chip")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, JAX reports "
                 f"{len(devices)}")
    return devices


def device_report(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


# ------------------------------------------------------------ deployments
def kronecker(scale: int, seed: int):
    from repro.core.generators import rmat
    t0 = time.perf_counter()
    g = rmat(scale, edge_factor=16, a=0.57, b=0.19, c=0.19, seed=seed,
             name=f"kron{scale}")
    log(f"graph: Graph500 Kronecker scale {scale}: V={g.num_vertices} "
        f"E={g.num_edges} generated in {time.perf_counter() - t0:.2f}s")
    return g


def search_corpus(n: int, seed: int):
    from repro.core.generators import clustered_vectors
    from repro.search import build_nsw_graph
    vecs, _ = clustered_vectors(n, dim=KNN_DIM, num_clusters=16, seed=seed)
    # on an integer grid every float32 squared distance is an exact sum,
    # so the device's distances equal the host mirror's in any order
    vecs = np.round(vecs * KNN_GRID).astype(np.float32)
    t0 = time.perf_counter()
    g = build_nsw_graph(vecs, k=KNN_K_OUT, name="nsw")
    log(f"corpus: {n} x {KNN_DIM} clustered vectors, NSW k_out={KNN_K_OUT} "
        f"built in {time.perf_counter() - t0:.2f}s")
    return vecs, g


def knn_queries(vecs: np.ndarray, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    q = vecs[rng.integers(0, len(vecs), n)]
    noise = np.round(rng.normal(0.0, 0.05 * KNN_GRID, q.shape))
    return (q + noise).astype(np.float32)


def close(got: np.ndarray, want: np.ndarray) -> bool:
    """The tolerance float answers (PR, BC) are held to: float32 sums
    taken in another order than the reference's."""
    return np.allclose(got, want, rtol=1e-3,
                       atol=1e-6 * float(np.abs(want).max()))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def recall_at(got: np.ndarray, want: np.ndarray) -> float:
    k = want.shape[1]
    return float(np.mean([len(set(g.tolist()) & set(w.tolist())) / k
                          for g, w in zip(got, want)]))


# ---------------------------------------------------------------- serving
def _served(futures: dict) -> dict:
    """Resolve every future (raises on a failed one) and sum the wall of
    each distinct launch per kernel."""
    out, walls = {}, {}
    for name, futs in futures.items():
        out[name] = [f.result() for f in futs]
        launches = {f.telemetry["launch_index"]:
                    f.telemetry["launch_wall_seconds"] for f in futs}
        walls[name] = {"launches": len(launches),
                       "seconds": sum(launches.values())}
    return out, walls


def analytics_round(session, gid: str, sources: dict) -> tuple[dict, dict]:
    """One mixed burst on the graph, served by one flush."""
    futures = {
        "bfs": [session.enqueue(gid, "bfs", [s]) for s in sources["bfs"]],
        "sssp": [session.enqueue(gid, "sssp", [s]) for s in sources["sssp"]],
        "cc": [session.enqueue(gid, "cc")],
        "pr": [session.enqueue(gid, "pr"), session.enqueue(gid, "pr")],
        "bc": [session.enqueue(gid, "bc", sources["bc"])],
    }
    session.flush(gid)
    return _served(futures)


def knn_round(session, gid: str, queries: np.ndarray):
    futures = {"knn": [session.enqueue(gid, "knn", queries[i:i + 1])
                       for i in range(len(queries))]}
    session.flush(gid)
    out, walls = _served(futures)
    return np.concatenate(out["knn"]), walls


def check_analytics(g, sources: dict, out: dict, sample: int = 2) -> None:
    """Compare served answers with the host references on a sample."""
    from repro.algos.graph_arrays import edge_weights
    from repro.core.baselines import (bc_baseline, bfs_baseline, cc_baseline,
                                      pagerank_baseline, sssp_baseline)
    t0 = time.perf_counter()
    for i in range(sample):
        s = sources["bfs"][i * (BURST - 1)]
        row = out["bfs"][i * (BURST - 1)][0]
        check(np.array_equal(row, bfs_baseline(g, s)),
              f"bfs from {s} == bfs_baseline")
    weights = edge_weights(g.edge_src, g.indices)
    s = sources["sssp"][0]
    check(np.array_equal(out["sssp"][0][0].astype(np.int64),
                         sssp_baseline(g, weights, s)),
          f"sssp from {s} == sssp_baseline")
    check(np.array_equal(out["cc"][0], cc_baseline(g)), "cc == cc_baseline")
    pr_a, pr_b = out["pr"]
    check(np.array_equal(pr_a, pr_b), "both pr requests got one answer")
    want = pagerank_baseline(g)
    check(close(pr_a, want), f"pr ~= pagerank_baseline (max rel err "
                             f"{rel_err(pr_a, want):.3e})")
    got = out["bc"][0].sum(axis=0)
    want = bc_baseline(g, sources["bc"])
    check(close(got, want), f"bc over {len(sources['bc'])} sources ~= "
                            f"bc_baseline (max rel err "
                            f"{rel_err(got, want):.3e})")
    log(f"references took {time.perf_counter() - t0:.2f}s on the host")


def check_recall(vecs: np.ndarray, queries: np.ndarray,
                 got: np.ndarray) -> None:
    """recall@k of served ids against exact search (`knn_brute_force`),
    held to the floor for the corpus's size."""
    from repro.search import knn_brute_force
    exact = knn_brute_force(vecs, queries, got.shape[1])
    recall = recall_at(got, exact)
    floor = (KNN_SMALL_RECALL_FLOOR if len(vecs) <= FOUR_CHIP_CORPUS
             else KNN_RECALL_FLOOR)
    check(recall >= floor, f"knn recall@{got.shape[1]} vs knn_brute_force "
                           f"{recall:.4f} >= {floor} (recall@1 "
                           f"{float(np.mean(got[:, 0] == exact[:, 0])):.4f})")


def check_knn(g, vecs: np.ndarray, queries: np.ndarray, entry,
              got: np.ndarray) -> None:
    """Served knn ids equal, on every query, the host mirror of the same
    beam search (`knn_search_baseline`), and reach the recall floor."""
    from repro.core.baselines import knn_search_baseline
    p = entry.search_params
    t0 = time.perf_counter()
    want = np.stack([knn_search_baseline(
        g, vecs, q, entry.entry_point, beam_width=p.beam_width,
        k_return=p.k_return, max_steps=p.max_steps)[0] for q in queries])
    check(np.array_equal(got, want),
          f"knn ids of {len(queries)} queries == knn_search_baseline")
    check_recall(vecs, queries, got)
    log(f"knn references took {time.perf_counter() - t0:.2f}s on the host")


def pick_sources(rng, g) -> dict:
    """Distinct sources, drawn from vertices with out-edges."""
    pool = np.flatnonzero(np.asarray(g.out_degree) > 0)
    picks = rng.choice(pool, 2 * BURST + BC_SOURCES, replace=False).tolist()
    return {"bfs": picks[:BURST], "sssp": picks[BURST:2 * BURST],
            "bc": picks[2 * BURST:]}


def one_chip(devices, scale: int = SCALE, corpus: int = KNN_CORPUS) -> None:
    from repro.engine import EngineSession

    rng = np.random.default_rng(SEED)
    if scale == SCALE:
        log(f"cut: Graph500 scale {SCALE_CUT}")
    g = kronecker(scale, SEED)
    profile_dir = tempfile.mkdtemp(prefix="chip_smoke_trace_")
    # a fixed expected volume below the policy's LOrder tier: LOrder is a
    # per-vertex host loop that does not finish at this scale; redecisions
    # are off so traffic cannot switch the graph to it mid-run
    session = EngineSession(redecide_min_queries=10**9,
                            profiler_dir=profile_dir)
    t0 = time.perf_counter()
    gid = session.register(g, "kron", expected_queries=16)
    reg = time.perf_counter() - t0
    entry = session.registry.get(gid)
    log(f"register kron: {reg:.2f}s on the host, scheme "
        f"{entry.decision.scheme!r} ({entry.decision.reason}), backend "
        f"{entry.backend}, bucket {entry.bucket_shape}, "
        f"{entry.handle.device_bytes / 1e9:.3f} GB estimated on device")
    single = session.executor.single
    caps = {k: single.source_cap(entry.handle, k) for k in ("bfs", "sssp",
                                                             "bc")}
    log(f"source caps from device memory: {caps}")

    # cold: the first launch of each kernel compiles. warm: the same
    # traffic again with the result cache emptied, so every kernel runs
    # on the device again from a compiled executable and must repeat
    # the cold answers exactly.
    sources = pick_sources(rng, g)
    answers = {}
    for name in ("cold", "warm"):
        session.result_cache.invalidate_graph(gid)
        dedup0 = session.scheduler.dedup_hits
        t0 = time.perf_counter()
        out, walls = analytics_round(session, gid, sources)
        log(f"{name} analytics burst: {time.perf_counter() - t0:.2f}s "
            f"end to end; per kernel {json.dumps(walls)}")
        check(walls["bfs"]["launches"] < BURST
              and walls["sssp"]["launches"] < BURST,
              f"{name}: {BURST} bfs + {BURST} sssp sources coalesced into "
              f"{walls['bfs']['launches']} + {walls['sssp']['launches']} "
              f"launches")
        check(walls["pr"]["launches"] == 1
              and session.scheduler.dedup_hits == dedup0 + 1,
              f"{name}: two pr requests deduplicated into one run")
        answers[name] = out
    check_analytics(g, sources, answers["cold"])
    check(all(np.array_equal(a, b)
              for k in answers["cold"]
              for a, b in zip(answers["cold"][k], answers["warm"][k])),
          "warm answers == cold answers")
    pr_path = single.telemetry()["pr_path"]
    log(f"pr served by the {pr_path!r} path")
    check(pr_path == "xla", "pr served by the XLA pull loop")

    # one short profiled launch: the trace must record without error
    session.result_cache.invalidate_graph(gid)
    check(session.start_profiler(), "profiler started")
    session.enqueue(gid, "cc").result()
    session.stop_profiler()
    traces = [f for _, _, fs in os.walk(profile_dir) for f in fs
              if f.endswith(".xplane.pb")]
    check(session.profiler.error is None and bool(traces),
          f"profiler trace written ({len(traces)} xplane file(s))")
    shutil.rmtree(profile_dir)

    vecs, nsw = search_corpus(corpus, SEED)
    t0 = time.perf_counter()
    kid = session.register(nsw, "nsw", vectors=vecs, expected_queries=16)
    log(f"register nsw: {time.perf_counter() - t0:.2f}s, scheme "
        f"{session.registry.get(kid).decision.scheme!r}")
    queries = knn_queries(vecs, KNN_QUERIES, SEED + 1)
    got = {}
    for name in ("cold", "warm"):
        session.result_cache.invalidate_graph(kid)
        t0 = time.perf_counter()
        got[name], walls = knn_round(session, kid, queries)
        log(f"{name} knn burst: {time.perf_counter() - t0:.2f}s end to "
            f"end; {json.dumps(walls)}")
    check_knn(nsw, vecs, queries, session.registry.get(kid), got["cold"])
    check(np.array_equal(got["cold"], got["warm"]),
          "warm knn answers == cold knn answers")

    session.close()
    t = session.telemetry()["scheduler"]
    check(t["requests_failed"] == 0 and t["launches_failed"] == 0,
          f"no failed futures ({t['requests_served']} served, "
          f"{t['launches']} launches)")
    log(f"device memory_stats: {devices[0].memory_stats()}")


def four_chips(devices, scale: int = FOUR_CHIP_SCALE,
               corpus: int = FOUR_CHIP_CORPUS) -> None:
    """ShardedBackend across the four chips vs the single-device path on
    one of them, for every served kernel."""
    from repro.core.baselines import bc_baseline, pagerank_baseline
    from repro.engine import EngineSession

    rng = np.random.default_rng(SEED)
    g = kronecker(scale, SEED)
    vecs, nsw = search_corpus(corpus, SEED)
    sharded = EngineSession(device_budget_bytes=1,
                            redecide_min_queries=10**9)
    single = EngineSession(redecide_min_queries=10**9)
    ids = {}
    for name, s in (("sharded", sharded), ("single", single)):
        t0 = time.perf_counter()
        ids[name] = (s.register(g, "kron", expected_queries=16),
                     s.register(nsw, "nsw", vectors=vecs,
                                expected_queries=16))
        e = s.registry.get(ids[name][0])
        log(f"{name}: registered in {time.perf_counter() - t0:.2f}s, "
            f"backend {e.backend}, scheme {e.decision.scheme!r}")
    check(sharded.registry.get(ids["sharded"][0]).backend == "sharded"
          and sharded.registry.get(ids["sharded"][1]).backend == "sharded",
          "both graphs placed sharded")
    mesh = sharded.executor.sharded.mesh
    check(mesh.devices.size == len(devices),
          f"sharded mesh spans all {len(devices)} chips")
    sources = pick_sources(rng, g)
    queries = knn_queries(vecs, KNN_QUERIES, SEED + 1)
    answers = {}
    for name, s in (("sharded", sharded), ("single", single)):
        gid, kid = ids[name]
        t0 = time.perf_counter()
        out, walls = analytics_round(s, gid, sources)
        out["knn"], kw = knn_round(s, kid, queries)
        walls.update(kw)
        log(f"{name}: served in {time.perf_counter() - t0:.2f}s; "
            f"{json.dumps(walls)}")
        answers[name] = out
    a, b = answers["sharded"], answers["single"]
    for k in ("bfs", "sssp", "cc", "knn"):
        check(all(np.array_equal(x, y) for x, y in zip(a[k], b[k])),
              f"sharded {k} == single-device {k}")
    check_recall(vecs, queries, a["knn"])
    # float answers: each path against the host references first, then
    # against each other, all at the references' tolerance
    want = {"pagerank_baseline": pagerank_baseline(g),
            "bc_baseline": bc_baseline(g, sources["bc"])}
    for name, out in answers.items():
        got = {"pagerank_baseline": out["pr"][0],
               "bc_baseline": out["bc"][0].sum(axis=0)}
        for ref, w in want.items():
            check(close(got[ref], w), f"{name} ~= {ref} (max rel err "
                                      f"{rel_err(got[ref], w):.3e})")
    for k in ("pr", "bc"):
        check(close(a[k][0], b[k][0]), f"sharded {k} ~= single-device {k} "
                                       f"(max rel err "
                                       f"{rel_err(a[k][0], b[k][0]):.3e})")
    ex = sharded.executor.sharded.telemetry()
    log(f"sharded dispatches {ex['dispatches']}, exchange steps "
        f"{ex['hot_prefix']['steps']}")
    for s in (sharded, single):
        s.close()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    log(f"per-chip peak_bytes_in_use: {peaks}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-chip host")
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind} x {len(devices)} "
        f"({devices[0].platform})")
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(devices)
    log(f"total {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": device_report(devices)}),
          flush=True)


if __name__ == "__main__":
    main()
