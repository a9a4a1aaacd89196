"""Engine harness — policy decisions, amortization, and the closed loop.

Phases:

1. **Decisions + amortization** — for each dataset: register with the
   serving engine (policy decides a scheme from probes + volume hint),
   then measure batched multi-source BFS latency on the *original* vs the
   *served* layout directly, and report the wall-clock break-even query
   count next to the ledger's cache-model estimate. Each registration's
   realized gain also feeds the strength calibrator.
2. **Online re-decision** — serve a synthetic bursty workload whose
   realized volume diverges from its registration hint and report the
   re-decisions the session makes (original -> cheap tier -> LOrder).
3. **Decisions after calibration** — replay a recorded outcome stream in
   which LOrder keeps realizing almost nothing (the misprediction regime
   Faldu et al. document), then re-run the policy on every dataset's
   probes: decisions that flip show the calibrated strengths overriding
   the static tree.
4. **Shape bucketing** — serve a stream of distinct-shape graphs through
   an exact-shape executor and a bucketed one; report the compile-miss
   reduction and check bucketed results are bit-identical.
5. **Sharded serving parity** — in a subprocess with 4 forced host
   devices, register a graph whose CSR footprint exceeds the device
   budget and serve **all six kernels** through ``EngineSession.submit``;
   report per-device memory, wall-clock per kernel, and parity against a
   single-device session serving the same graph (bit-identical for
   bfs/sssp/cc/ccsv, allclose for pr/bc).
6. **Hot-prefix exchange** — same 4-device mesh, hub-packed layout: run
   the sharded traversals with and without ``hot_prefix_fraction`` and
   report per-step exchanged bytes, the savings fraction, and the static
   prefix hit rate — results must stay bit-identical either way.
7. **Fused traversal loop** — same 4-device mesh: the fused on-device
   ``XLA::While`` drivers vs the host step loop, per kernel — dispatches
   per query (O(steps) -> O(1)), post-compile wall/step, bit-identical
   results (the ROADMAP item 1 receipt).
8. **Scheduler throughput** — a 16-request multi-source burst on one
   graph served two ways: sequential blocking ``submit`` (one device
   launch per request) vs the request plane (``enqueue`` + ``drain``,
   requests coalesced into shared vmapped launches). Reports device
   launches and wall per query for both, with per-request parity.
9. **Observability** — a 64-request mixed-kernel burst through one
   session; p50/p99 queue-wait and serve latency from the engine's own
   histograms, plus a structurally validated Chrome trace export.
10. **Sustained load** — open-loop Poisson arrivals against the
    always-on plane: Zipf-over-degree sources at ~0.5x measured
    capacity with the result cache on vs off (hit rate, launches per
    query, latency percentiles, bit-identity sampling), then ~3.5x
    capacity with and without bounded-queue admission control (p99
    queue wait bounded vs saturated, rejects counted).
11. **Churn** — sustained Zipf load with concurrent edge churn through
    ``update_graph``: the incremental patch tier re-permutes per delta
    at a wall cost >= 10x below a measured full LOrder pass, serve p99
    stays bounded across generations, and post-churn results stay
    bit-identical to a fresh session on the final mutated graph.
12. **knn** — the search workload (docs/search.md): a Zipf query mix
    over a clustered NSW corpus served through ``enqueue``, recall@10
    against brute force, serve p50/p99, the visit-telemetry reorder
    loop (``refresh_hotness``: full visitsort then the patch tier), and
    a simulated vector-cache miss-rate comparison of identity vs
    degree-ordered vs visit-ordered layouts — degree is uniform on
    search graphs, so the observed-visit layout must win.

Emits benchmarks/results/engine.json.
"""
from __future__ import annotations

import json
import textwrap

import numpy as np

from .common import (bench_suite, fmt_table, run_forced_four_devices,
                     save_json, time_call)


def _phase_decisions(session, suite, batch, repeats):
    from repro.algos.graph_arrays import to_device

    rng = np.random.default_rng(0)
    rows = []
    for dname, g in suite.items():
        gid = session.register(g, graph_id=dname, expected_queries=256)
        entry = session.registry.get(gid)
        srcs = rng.integers(0, g.num_vertices, size=batch).astype(np.int32)

        # both layouts timed through the same exact-shape path, so the
        # comparison isolates the *reordering* effect — the served
        # handle's bucket padding would otherwise be booked as loss
        ga_orig = to_device(g)
        ga_served = to_device(entry.served, canonical_ids=entry.inv_perm)
        srcs_served = entry.perm[srcs].astype(np.int32)
        t_before, _ = time_call(session.executor.run, ga_orig, "bfs", srcs,
                                repeats=repeats)
        t_after, _ = time_call(session.executor.run, ga_served, "bfs",
                               srcs_served, repeats=repeats)
        saving = t_before - t_after
        # "never amortizes" is encoded as null + a flag, not Infinity —
        # strict JSON (common.save_json) has no spelling for infinity
        never = saving <= 1e-9
        wall_break_even = None if never else entry.reorder_seconds / saving
        rec = next(r for r in session.policy.history if r.graph_id == gid)
        rows.append({
            "dataset": dname,
            "scheme": entry.decision.scheme,
            "kwargs": entry.decision.kwargs,
            "reason": entry.decision.reason,
            "reorder_seconds": round(entry.reorder_seconds, 4),
            "predicted_gain": rec.decision.predicted_gain,
            "realized_gain": round(rec.realized_gain, 4),
            "batch": int(batch),
            "query_seconds_before": round(t_before, 5),
            "query_seconds_after": round(t_after, 5),
            "wall_break_even_queries": (None if never
                                        else round(wall_break_even, 1)),
            "wall_break_even_never": never,
        })
        print(f"[engine] {dname}: {entry.decision.scheme} "
              f"{entry.decision.kwargs}, reorder "
              f"{entry.reorder_seconds:.2f}s, query "
              f"{t_before * 1e3:.1f}ms -> {t_after * 1e3:.1f}ms", flush=True)
    return rows


def _phase_redecision(session, scale):
    """Bursty workload: hint says 2 queries, reality delivers ~40."""
    from repro.core.generators import powerlaw_community

    g = powerlaw_community(max(2000, int(20_000 * scale)), avg_degree=12.0,
                           mixing=0.1, seed=21, name="burst")
    gid = session.register(g, graph_id="burst", expected_queries=2)
    entry = session.registry.get(gid)
    first = entry.decision.scheme
    rng = np.random.default_rng(5)
    for _ in range(40):
        session.submit(gid, "bfs", rng.integers(0, g.num_vertices, size=4))
    events = [e for e in session.redecision_log if e["graph_id"] == gid]
    print(f"[engine] burst workload: hint=2, served "
          f"{entry.queries_observed} batches, {len(events)} re-decisions: "
          + " -> ".join([first] + [e["new_scheme"] for e in events]),
          flush=True)
    return {
        "dataset": "burst",
        "expected_queries_hint": 2,
        "queries_observed": entry.queries_observed,
        "scheme_path": [first] + [e["new_scheme"] for e in events],
        "redecision_count": len(events),
        "events": events,
    }


def _phase_calibration_flip(session, suite):
    """Replay outcomes where LOrder collapses; re-decide every dataset."""
    policy = session.policy
    pre = {d: policy.decide(session.registry.get(d).probes, 256).scheme
           for d in suite}
    from repro.engine import PolicyDecision, ReorderPolicy

    probes = session.registry.get("burst").probes
    skew = ReorderPolicy._skew(probes)
    lorder = PolicyDecision("lorder", {}, "replayed historical decision",
                            0.75 * skew, skew)
    for i in range(25):
        # recorded outcome: near-zero realized reduction despite high skew
        policy.record(f"replay-{i}", lorder, miss_rate_before=0.5,
                      miss_rate_after=0.49, reorder_seconds=1.0)
    post = {d: policy.decide(session.registry.get(d).probes, 256).scheme
            for d in suite}
    changed = {d: (pre[d], post[d]) for d in suite if pre[d] != post[d]}
    cal = policy.calibrator
    print(f"[engine] after calibration replay: lorder strength "
          f"{cal.strength('lorder'):.3f} (prior 0.75), "
          f"{len(changed)} decision(s) changed: "
          + (", ".join(f"{d}: {a}->{b}" for d, (a, b) in changed.items())
             or "none"), flush=True)
    return {
        "strengths_after": cal.strengths(),
        "decisions_before": pre,
        "decisions_after": post,
        "changed": {d: list(v) for d, v in changed.items()},
    }


def _phase_bucketing(scale, batch: int = 4):
    """Distinct-shape graph stream: exact-shape vs bucketed compile counts."""
    from repro.core.generators import powerlaw_community
    from repro.engine import BatchedExecutor

    sizes = [int(n * max(scale, 0.25) / 0.5)
             for n in (1100, 1250, 1400, 1550, 1750, 1950)]
    graphs = [powerlaw_community(n, avg_degree=8.0, seed=100 + i,
                                 name=f"stream-{n}")
              for i, n in enumerate(sizes)]
    assert len({(g.num_vertices, g.num_edges) for g in graphs}) == len(graphs)

    exact = BatchedExecutor(bucketing=False)
    bucketed = BatchedExecutor()
    rng = np.random.default_rng(9)
    identical = True
    for g in graphs:
        srcs = rng.integers(0, g.num_vertices, size=batch).astype(np.int32)
        out_e = np.asarray(exact.run(exact.prepare(g), "bfs", srcs))
        out_b = np.asarray(bucketed.run(bucketed.prepare(g), "bfs", srcs))
        identical &= bool(np.array_equal(out_e, out_b))
    m_exact = exact.single.cache_misses
    m_bucket = bucketed.single.cache_misses
    buckets = bucketed.single.telemetry()["bucketing"]
    print(f"[engine] bucketing: {len(graphs)} distinct shapes -> "
          f"{m_exact} exact-shape compile misses vs {m_bucket} bucketed "
          f"({m_exact / max(m_bucket, 1):.1f}x fewer), "
          f"{buckets['distinct_buckets']} bucket(s), "
          f"bit-identical={identical}", flush=True)
    return {
        "graph_shapes": [[g.num_vertices, g.num_edges] for g in graphs],
        "compile_misses_exact": m_exact,
        "compile_misses_bucketed": m_bucket,
        "compile_reduction_x": round(m_exact / max(m_bucket, 1), 2),
        "buckets": buckets,
        "bit_identical": identical,
    }


def _run_four_devices(prog: str):
    """Run ``prog`` on 4 forced host devices; returns the json after its
    RESULT line or an error dict.

    The child is pinned to the CPU, so its timings are CPU timings. On a
    machine with an accelerator that would pass them off as the device's
    (and the parent already holds the chip), so this refuses to run
    unless the parent's own backend is the CPU.
    """
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"this phase runs on 4 forced CPU devices, but this process "
            f"serves on {jax.default_backend()!r}; its numbers would be "
            f"CPU numbers. Run it with JAX_PLATFORMS=cpu, or use "
            f"`python chip_smoke.py --four-chips` for the chips")
    res = run_forced_four_devices(["-c", prog], timeout=900)
    if res.returncode != 0:
        return {"error": res.stderr[-2000:]}
    line = next(l for l in res.stdout.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _phase_sharded(scale):
    """4 forced host devices: serve an over-budget graph end-to-end —
    all six kernels, with parity against a single-device session serving
    the same graph (same policy => same reorder => cc/ccsv label spaces
    line up bit-for-bit)."""
    n = max(2000, int(20_000 * scale))
    prog = textwrap.dedent(f"""
        import json, time
        import numpy as np
        import jax, jax.numpy as jnp
        assert jax.device_count() == 4, jax.devices()
        from repro.core.generators import powerlaw_community
        from repro.engine import EngineSession, estimate_device_bytes

        g = powerlaw_community({n}, avg_degree=10.0, seed=31, name="big")
        budget = estimate_device_bytes(g.num_vertices, g.num_edges) // 2
        session = EngineSession(device_budget_bytes=budget,
                                redecide_min_queries=10**6)
        gid = session.register(g, expected_queries=256)
        entry = session.registry.get(gid)
        assert entry.backend == "sharded", entry.backend
        ref = EngineSession(redecide_min_queries=10**6)  # single-device
        rid = ref.register(g, graph_id="ref", expected_queries=256)
        srcs = np.arange(4) * (g.num_vertices // 5)
        walls, parity = {{}}, {{}}
        for kernel in ("bfs", "sssp", "bc", "pr", "cc", "ccsv"):
            args = (srcs,) if kernel in ("bfs", "sssp", "bc") else ()
            t0 = time.perf_counter()
            out = session.submit(gid, kernel, *args)
            walls[kernel] = time.perf_counter() - t0
            want = ref.submit(rid, kernel, *args)
            if kernel in ("pr", "bc"):
                parity[kernel] = bool(np.allclose(out, want,
                                                  rtol=1e-3, atol=1e-3))
            else:
                parity[kernel] = bool(np.array_equal(
                    np.asarray(out), np.asarray(want)))
        hp = session.executor.sharded.telemetry()["hot_prefix"]
        print("RESULT " + json.dumps({{
            "num_vertices": g.num_vertices,
            "num_edges": g.num_edges,
            "device_budget_bytes": budget,
            "graph_bytes": estimate_device_bytes(g.num_vertices,
                                                 g.num_edges),
            "per_device_bytes": entry.handle.device_bytes,
            "num_shards": session.executor.sharded.num_shards,
            "hot_prefix_fraction": entry.hot_prefix_fraction,
            "wall_seconds": {{k: round(v, 4) for k, v in walls.items()}},
            "parity": parity,
            "ledger_backend": entry.ledger.backend,
            "gain_discount": entry.ledger.gain_discount,
            "exchange": hp,
        }}))
    """)
    out = _run_four_devices(prog)
    if "error" in out:
        print(f"[engine] sharded phase FAILED:\n{out['error']}", flush=True)
        return out
    print(f"[engine] sharded: V={out['num_vertices']} across "
          f"{out['num_shards']} devices "
          f"(~{out['per_device_bytes'] / 1e6:.2f} MB/device vs "
          f"{out['graph_bytes'] / 1e6:.2f} MB whole), walls "
          + ", ".join(f"{k}={v * 1e3:.0f}ms"
                      for k, v in out["wall_seconds"].items())
          + f", parity={out['parity']}", flush=True)
    return out


def _phase_hot_prefix(scale):
    """4 forced host devices, hub-packed layout: per-step exchanged bytes
    with the hot-prefix exchange vs the full all-gather, at bit-identical
    results (SSSP + CC: int32 state either way, so the comparison is
    apples-to-apples; frontier BFS exchanges a bool frontier instead and
    is reported for context)."""
    n = max(2000, int(20_000 * scale))
    prog = textwrap.dedent(f"""
        import json
        import numpy as np
        import jax
        assert jax.device_count() == 4, jax.devices()
        from repro.core.baselines import dbg_order
        from repro.core.dist import (ExchangeStats, make_distributed_cc,
                                     make_distributed_sssp, vertex_mesh)
        from repro.core.generators import powerlaw_community

        g0 = powerlaw_community({n}, avg_degree=10.0, seed=31)
        perm = np.asarray(dbg_order(g0))
        g = g0.apply_permutation(perm)     # hubs packed into the prefix
        inv = np.empty_like(perm); inv[perm] = np.arange(len(perm))
        mesh = vertex_mesh(4)
        srcs = np.arange(4) * (g.num_vertices // 5)
        out = {{}}
        for kernel, frac in (("sssp", 0.15), ("cc", 0.15)):
            full, hot = ExchangeStats(), ExchangeStats()
            if kernel == "sssp":
                run_f = make_distributed_sssp(g, mesh, canonical_ids=inv,
                                              stats=full)
                run_h = make_distributed_sssp(g, mesh, canonical_ids=inv,
                                              hot_prefix_fraction=frac,
                                              cold_every=5, stats=hot)
                a, b = run_f(srcs), run_h(srcs)
            else:
                run_f = make_distributed_cc(g, mesh, stats=full)
                run_h = make_distributed_cc(g, mesh,
                                            hot_prefix_fraction=frac,
                                            cold_every=5, stats=hot)
                a, b = run_f(), run_h()
            assert np.array_equal(np.asarray(a), np.asarray(b)), kernel
            out[kernel] = {{
                "hot_prefix_fraction": frac,
                "prefix_hit_rate": round(run_h.prefix_hit_rate, 4),
                "bytes_per_step_full": round(full.bytes_per_step, 1),
                "bytes_per_step_hot": round(hot.bytes_per_step, 1),
                "steps_full_variant": full.steps,
                "steps_hot_variant": hot.steps,
                "savings_fraction": round(hot.savings_fraction, 4),
                "smaller_per_step": hot.bytes_per_step
                                    < full.bytes_per_step,
                "bit_identical": True,
            }}
        print("RESULT " + json.dumps(out))
    """)
    out = _run_four_devices(prog)
    if "error" in out:
        print(f"[engine] hot-prefix phase FAILED:\n{out['error']}",
              flush=True)
        return out
    for kernel, r in out.items():
        print(f"[engine] hot-prefix {kernel}: "
              f"{r['bytes_per_step_full']:.0f} B/step full -> "
              f"{r['bytes_per_step_hot']:.0f} B/step hot "
              f"({100 * r['savings_fraction']:.0f}% fewer bytes vs "
              f"all-full, hit rate {r['prefix_hit_rate']:.2f}, "
              f"bit-identical={r['bit_identical']})", flush=True)
    return out


def _phase_scheduler(scale, requests: int = 16, sources_each: int = 2):
    """Request-plane throughput: the same multi-source burst served
    sequentially (blocking submit, one launch per request) vs coalesced
    (enqueue + drain, shared vmapped launches)."""
    import time

    from repro.core.generators import powerlaw_community
    from repro.engine import EngineSession

    n = max(2000, int(20_000 * scale))
    g = powerlaw_community(n, avg_degree=10.0, seed=41, name="front")
    rng = np.random.default_rng(17)
    bursts = [rng.integers(0, n, size=sources_each) for _ in range(requests)]

    # result_cache=False on both sides: this phase measures pure request
    # coalescing, and the warm-up submits would otherwise pre-populate the
    # burst's sources and corrupt the launch counts (the cache gets its
    # own sustained phase).
    seq = EngineSession(redecide_min_queries=10**6, result_cache=False)
    sid = seq.register(g, graph_id="seq", expected_queries=256)
    seq.submit(sid, "bfs", bursts[0])            # warm the per-request shape
    launches0 = seq.executor.queries_run
    t0 = time.perf_counter()
    seq_outs = [np.asarray(seq.submit(sid, "bfs", b)) for b in bursts]
    seq_wall = time.perf_counter() - t0
    seq_launches = seq.executor.queries_run - launches0

    bat = EngineSession(redecide_min_queries=10**6, result_cache=False)
    bid = bat.register(g, graph_id="bat", expected_queries=256)
    bat.submit(bid, "bfs", np.concatenate(bursts))  # warm the coalesced shape
    launches0 = bat.executor.queries_run
    t0 = time.perf_counter()
    futs = [bat.enqueue(bid, "bfs", b) for b in bursts]
    bat.drain()
    bat_wall = time.perf_counter() - t0
    bat_launches = bat.executor.queries_run - launches0

    identical = all(np.array_equal(np.asarray(f.result()), want)
                    for f, want in zip(futs, seq_outs))
    reduction = seq_launches / max(bat_launches, 1)
    out = {
        "requests": requests,
        "sources_each": sources_each,
        "launches_sequential": seq_launches,
        "launches_coalesced": bat_launches,
        "launch_reduction_x": round(reduction, 2),
        "wall_per_query_sequential_ms": round(seq_wall / requests * 1e3, 3),
        "wall_per_query_coalesced_ms": round(bat_wall / requests * 1e3, 3),
        "wall_speedup_x": round(seq_wall / max(bat_wall, 1e-9), 2),
        "bit_identical": identical,
        "scheduler": bat.scheduler.telemetry(),
    }
    print(f"[engine] scheduler: {requests}-request burst -> "
          f"{seq_launches} launches sequential vs {bat_launches} coalesced "
          f"({reduction:.0f}x fewer), "
          f"{out['wall_per_query_sequential_ms']:.1f}ms -> "
          f"{out['wall_per_query_coalesced_ms']:.1f}ms per query "
          f"({out['wall_speedup_x']:.1f}x), bit-identical={identical}",
          flush=True)
    return out


def _phase_observability(scale, requests: int = 64):
    """Observability plane: a 64-request mixed-kernel burst through one
    session, reporting p50/p99 queue-wait and serve latencies from the
    engine's own histograms, and exporting the request trace as
    Perfetto-loadable Chrome trace JSON next to the results. The trace is
    structurally validated (nesting, envelope) and every served future's
    trace id must appear in it."""
    from repro.core.generators import powerlaw_community
    from repro.engine import EngineSession
    from repro.engine.obs import (merge_histogram_snapshots,
                                  validate_chrome_trace)

    from .common import RESULTS

    n = max(2000, int(20_000 * scale))
    g = powerlaw_community(n, avg_degree=10.0, seed=51, name="obs")
    session = EngineSession(redecide_min_queries=10**6)
    gid = session.register(g, graph_id="obs", expected_queries=256)
    rng = np.random.default_rng(23)
    kernels = ("bfs", "sssp", "bc", "pr", "cc", "ccsv")
    futs = []
    for i in range(requests):
        kernel = kernels[i % len(kernels)]
        srcs = (rng.integers(0, n, size=2)
                if kernel in ("bfs", "sssp", "bc") else None)
        # a third of the burst carries deadlines so the slack histogram
        # (and deadlines_missed attribution) exercises too
        dl = 5.0 if i % 3 == 0 else None
        futs.append(session.enqueue(gid, kernel, srcs,
                                    deadline_seconds=dl))
    session.drain()
    for f in futs:
        np.asarray(f.result())

    snap = session.metrics().snapshot()
    qw = snap["histograms"]["engine_queue_wait_seconds"]
    sv = snap["histograms"]["engine_serve_seconds"]
    overall_qw = merge_histogram_snapshots(list(qw.values()))
    overall_sv = merge_histogram_snapshots(list(sv.values()))
    assert overall_qw["count"] == requests, overall_qw["count"]
    assert overall_sv["count"] == requests, overall_sv["count"]
    per_kernel = {
        key.split("kernel=")[-1]: {
            "count": s["count"],
            "p50_ms": round(s["p50"] * 1e3, 3),
            "p99_ms": round(s["p99"] * 1e3, 3),
        } for key, s in sorted(sv.items())}

    trace_path = session.tracer.export(RESULTS / "engine_trace.json")
    trace = json.loads(trace_path.read_text())
    stats = validate_chrome_trace(trace)
    traced = {e["args"]["trace_id"] for e in trace["traceEvents"]
              if e.get("ph") == "X" and "trace_id" in e.get("args", {})}
    missing = [f.trace_id for f in futs if f.trace_id not in traced]
    assert not missing, f"futures missing from trace: {missing}"

    out = {
        "requests": requests,
        "queue_wait": {"count": overall_qw["count"],
                       "p50_ms": round(overall_qw["p50"] * 1e3, 3),
                       "p99_ms": round(overall_qw["p99"] * 1e3, 3)},
        "serve": {"count": overall_sv["count"],
                  "p50_ms": round(overall_sv["p50"] * 1e3, 3),
                  "p99_ms": round(overall_sv["p99"] * 1e3, 3)},
        "per_kernel_serve": per_kernel,
        "trace_file": trace_path.name,
        "trace": stats,
        "dropped_events": trace["otherData"]["dropped_events"],
        "scheduler": session.scheduler.telemetry(),
    }
    print(f"[engine] observability: {requests}-request burst, queue-wait "
          f"p50={out['queue_wait']['p50_ms']:.1f}ms "
          f"p99={out['queue_wait']['p99_ms']:.1f}ms, serve "
          f"p50={out['serve']['p50_ms']:.1f}ms "
          f"p99={out['serve']['p99_ms']:.1f}ms, trace {trace_path.name}: "
          f"{stats['complete_spans']} spans on {stats['tracks']} tracks",
          flush=True)
    return out


def _phase_sustained(scale, paced_requests: int = 160,
                     overload_requests: int = 200):
    """Sustained open-loop load against the always-on request plane.

    Three sub-experiments on one hub-heavy graph:

    * **capacity** — closed-loop unique-source burst through the plane
      (enqueue + drain) to measure the service capacity the open-loop
      runs are paced against.
    * **paced** (~0.5x capacity, Poisson arrivals, Zipf sources ranked
      by vertex degree) — the same arrival sequence served with the
      result cache on vs off; reports cache hit rate, device launches
      per query, and p50/p99 queue-wait and serve latency. A sample of
      cache-served rows is checked bit-identical against a fresh
      reference session.
    * **overload** (~3.5x capacity, deadline-carrying requests) — with
      no admission control the queue grows with the run and p99 wait
      saturates; with a bounded queue (reject on overflow) the plane
      sheds load and p99 stays bounded. Both sides run uncached so the
      comparison isolates admission.
    """
    import time

    from repro.core.generators import powerlaw_community
    from repro.engine import AdmissionPolicy, AdmissionRejected, EngineSession
    from repro.engine.obs import merge_histogram_snapshots

    n = max(2000, int(20_000 * scale))
    g = powerlaw_community(n, avg_degree=10.0, seed=61, name="sustained")
    rng = np.random.default_rng(29)
    # Zipf(1.5) ranks mapped onto degree-descending vertex order: the
    # popular sources are the hubs, which is both what real query logs
    # look like and what the GRASP-style hot-prefix pinning targets.
    by_degree = np.argsort(-np.asarray(g.out_degree, dtype=np.int64))
    zipf_pool = by_degree[(rng.zipf(1.5, size=4 * paced_requests) - 1) % n]

    def _fresh(**kw):
        kw.setdefault("redecide_min_queries", 10**6)
        kw.setdefault("max_delay", 0.005)
        s = EngineSession(**kw)
        s.register(g, graph_id="sus", expected_queries=4096)
        return s

    def _warm(session):
        # compile every power-of-two source bucket the runs can hit,
        # then wipe the warm-up rows so they can't inflate hit rates
        for k in (1, 2, 4, 8, 16):
            session.submit("sus", "bfs", np.arange(k))
        if session.result_cache is not None:
            session.result_cache.clear()

    def _paced(session, sources, offered_qps, deadline=None):
        """Open-loop arrivals; returns per-accepted-request (future,
        lateness) where lateness is how far behind the open-loop schedule
        the enqueue actually ran — a single-threaded generator slips when
        the plane serves inline, and ignoring that slip (coordinated
        omission) would hide saturation entirely."""
        arrivals = np.cumsum(rng.exponential(1.0 / offered_qps,
                                             size=len(sources)))
        futs, lates, rejected = [], [], 0
        t0 = time.perf_counter()
        for src, at in zip(sources, arrivals):
            lag = t0 + at - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            try:
                fut = session.enqueue("sus", "bfs", [int(src)],
                                      deadline_seconds=deadline)
            except AdmissionRejected:
                rejected += 1
                continue
            futs.append(fut)
            lates.append(max(0.0, time.perf_counter() - (t0 + at)))
        session.drain()
        return futs, lates, rejected, time.perf_counter() - t0

    def _corrected(futs, lates):
        """Schedule-corrected end-to-end latency: generator lateness plus
        the in-plane enqueue->served time the engine accounted."""
        e2e = [late + f.telemetry["queue_seconds"]
               for f, late in zip(futs, lates) if f.telemetry]
        return {
            "e2e_p50_ms": round(float(np.percentile(e2e, 50)) * 1e3, 1),
            "e2e_p99_ms": round(float(np.percentile(e2e, 99)) * 1e3, 1),
        } if e2e else {"e2e_p50_ms": None, "e2e_p99_ms": None}

    def _latency(session):
        snap = session.metrics().snapshot()["histograms"]
        row = {}
        for label, name in (("queue_wait", "engine_queue_wait_seconds"),
                            ("serve", "engine_serve_seconds")):
            s = merge_histogram_snapshots(list(snap.get(name, {}).values()))
            row[f"{label}_p50_ms"] = round((s.get("p50") or 0.0) * 1e3, 3)
            row[f"{label}_p99_ms"] = round((s.get("p99") or 0.0) * 1e3, 3)
        return row

    # --- capacity: closed-loop unique-source burst through the plane
    cap_s = _fresh(result_cache=False)
    _warm(cap_s)
    uniq = rng.choice(n, size=48, replace=False)
    t0 = time.perf_counter()
    for src in uniq:
        cap_s.enqueue("sus", "bfs", [int(src)])
    cap_s.drain()
    capacity_qps = len(uniq) / max(time.perf_counter() - t0, 1e-9)
    cap_s.close(drain=False)

    # --- paced: cached vs uncached on the identical Zipf arrival stream
    offered = 0.5 * capacity_qps
    sources = zipf_pool[:paced_requests]
    paced = {"offered_qps": round(offered, 1), "requests": paced_requests}
    cached_futs = None
    for label, kw in (("cached", {}), ("uncached", {"result_cache": False})):
        s = _fresh(**kw)
        _warm(s)
        hits0 = s.result_cache.hits if s.result_cache else 0
        miss0 = s.result_cache.misses if s.result_cache else 0
        launches0 = s.executor.queries_run
        futs, lates, _, wall = _paced(s, sources, offered)
        launches = s.executor.queries_run - launches0
        row = {
            "launches": launches,
            "launches_per_query": round(launches / paced_requests, 4),
            "wall_seconds": round(wall, 3),
            **_corrected(futs, lates),
            **_latency(s),
        }
        if s.result_cache is not None:
            hits = s.result_cache.hits - hits0
            misses = s.result_cache.misses - miss0
            row["cache_hit_rate"] = round(hits / max(hits + misses, 1), 4)
            row["cache"] = s.result_cache.stats()
            cached_futs = futs
        paced[label] = row
        s.close(drain=False)
    # cache-served rows must be bit-identical to fresh execution
    ref = _fresh(result_cache=False)
    picks = rng.choice(paced_requests, size=min(12, paced_requests),
                       replace=False)
    paced["bit_identical"] = all(
        np.array_equal(np.asarray(cached_futs[i].result()),
                       np.asarray(ref.submit("sus", "bfs",
                                             [int(sources[i])])))
        for i in picks)
    ref.close(drain=False)

    # --- overload: no admission vs a bounded queue, deadlines attached
    over_offered = 3.5 * capacity_qps
    over_sources = rng.integers(0, n, size=overload_requests)
    overload = {"offered_qps": round(over_offered, 1),
                "requests": overload_requests}
    policies = (("no_admission", None),
                ("admission", AdmissionPolicy(max_pending=32,
                                              overload="reject")))
    for label, pol in policies:
        s = _fresh(result_cache=False, max_delay=10.0, admission=pol)
        _warm(s)
        futs, lates, rejected, wall = _paced(s, over_sources, over_offered,
                                             deadline=0.08)
        tel = s.scheduler.telemetry()
        overload[label] = {
            "served": tel["requests_served"],
            "rejected": rejected,
            "deadlines_missed": tel["deadlines_missed"],
            "wall_seconds": round(wall, 3),
            **_corrected(futs, lates),
            **_latency(s),
        }
        s.close(drain=False)
    overload["p99_bounded"] = (overload["admission"]["e2e_p99_ms"]
                               < overload["no_admission"]["e2e_p99_ms"])

    out = {"capacity_qps": round(capacity_qps, 1), "paced": paced,
           "overload": overload}
    print(f"[engine] sustained: capacity {capacity_qps:.0f} qps; paced "
          f"@{offered:.0f} qps hit-rate "
          f"{paced['cached']['cache_hit_rate']:.2f}, launches/query "
          f"{paced['cached']['launches_per_query']:.3f} cached vs "
          f"{paced['uncached']['launches_per_query']:.3f} uncached, "
          f"bit-identical={paced['bit_identical']}; overload "
          f"@{over_offered:.0f} qps e2e p99 "
          f"{overload['no_admission']['e2e_p99_ms']:.0f}ms open vs "
          f"{overload['admission']['e2e_p99_ms']:.0f}ms bounded "
          f"({overload['admission']['rejected']} rejected)", flush=True)
    return out


def _phase_fused(scale):
    """4 forced host devices: the fused on-device traversal loop vs the
    host step loop, per kernel — dispatches per query (O(steps) -> O(1)),
    post-compile wall clock and wall/step, at bit-identical results.
    This is the ROADMAP item 1 receipt: the engine stops being
    dispatch-bound before the reorder's locality gain can show up."""
    n = max(2000, int(20_000 * scale))
    prog = textwrap.dedent(f"""
        import json, time
        import numpy as np
        import jax
        assert jax.device_count() == 4, jax.devices()
        from repro.core.dist import (ExchangeStats, make_distributed_bc,
                                     make_distributed_bfs,
                                     make_distributed_cc,
                                     make_distributed_pagerank,
                                     make_distributed_sssp, vertex_mesh)
        from repro.core.generators import powerlaw_community

        g = powerlaw_community({n}, avg_degree=10.0, seed=31)
        mesh = vertex_mesh(4)
        srcs = np.arange(4) * (g.num_vertices // 5)

        def build(kernel, stats, fused):
            if kernel == "pr":
                return make_distributed_pagerank(g, mesh, stats=stats,
                                                 fused=fused)[0]
            if kernel == "bc":
                return make_distributed_bc(g, mesh, stats=stats,
                                           fused=fused)
            f = dict(bfs=make_distributed_bfs, sssp=make_distributed_sssp,
                     cc=make_distributed_cc)[kernel]
            return f(g, mesh, hot_prefix_fraction=0.15, cold_every=5,
                     stats=stats, fused=fused)

        out = {{}}
        for kernel in ("bfs", "sssp", "cc", "pr", "bc"):
            res, row = {{}}, {{}}
            for mode in ("host", "fused"):
                stats = ExchangeStats()
                run = build(kernel, stats, mode == "fused")
                args = (srcs,) if kernel in ("bfs", "sssp", "bc") else ()
                jax.block_until_ready(run(*args))   # compile + warm
                before = stats.snapshot()
                t0 = time.perf_counter()
                res[mode] = np.asarray(jax.block_until_ready(run(*args)))
                wall = time.perf_counter() - t0
                d = stats.delta(before)
                row[mode] = {{
                    "wall_seconds": round(wall, 5),
                    "steps": d.steps,
                    "dispatches_per_query": d.dispatches,
                    "wall_per_step_ms": round(
                        wall * 1e3 / max(d.steps, 1), 4),
                }}
            row["bit_identical"] = bool(np.array_equal(res["host"],
                                                       res["fused"]))
            row["single_xla_while"] = \\
                row["fused"]["dispatches_per_query"] == 1
            out[kernel] = row
        print("RESULT " + json.dumps(out))
    """)
    out = _run_four_devices(prog)
    if "error" in out:
        print(f"[engine] fused phase FAILED:\n{out['error']}", flush=True)
        return out
    for kernel, r in out.items():
        print(f"[engine] fused {kernel}: dispatches/query "
              f"{r['host']['dispatches_per_query']} -> "
              f"{r['fused']['dispatches_per_query']}, wall/step "
              f"{r['host']['wall_per_step_ms']:.2f}ms -> "
              f"{r['fused']['wall_per_step_ms']:.2f}ms "
              f"({r['host']['steps']} steps, bit-identical="
              f"{r['bit_identical']})", flush=True)
    return out


def _phase_churn(scale, rounds: int = 8, queries_per_round: int = 12):
    """Sustained Zipf load with concurrent edge churn (dynamic graphs).

    One hub-heavy graph registered at high expected volume (a locality
    layout with a packed hot prefix), then ``rounds`` of: a burst of
    Zipf-over-degree BFS requests through the request plane, followed by
    an ``update_graph`` delta (remove random existing edges, add the
    same count of random ones) served by the **incremental patch tier**.
    Reports the patch-tier reorder wall against a measured full LOrder
    pass on the final graph (the acceptance bar is >= 10x cheaper),
    serve-latency percentiles across the churning run, and bit-identity
    of post-churn results against a fresh session registered directly on
    the final mutated graph.
    """
    import time

    from repro.core.lorder import lorder
    from repro.engine import EngineSession
    from repro.core.generators import powerlaw_community
    from repro.engine.obs import merge_histogram_snapshots

    n = max(1500, int(12_000 * scale))
    g = powerlaw_community(n, avg_degree=10.0, seed=71, name="churn")
    churn_edges = max(64, n // 25)
    rng = np.random.default_rng(37)
    by_degree = np.argsort(-np.asarray(g.degree, dtype=np.int64))

    s = EngineSession(redecide_min_queries=10**9, async_full_reorder=False)
    s.register(g, graph_id="churn", expected_queries=4096)
    entry = s.registry.get("churn")
    s.submit("churn", "bfs", np.arange(8))          # warm the compile

    patch_walls, mutate_walls = [], []
    for _ in range(rounds):
        srcs = by_degree[(rng.zipf(1.5, size=queries_per_round) - 1) % n]
        futs = [s.enqueue("churn", "bfs", [int(x)]) for x in srcs]
        s.flush()
        assert all(f.done() for f in futs)
        eidx = rng.choice(entry.graph.num_edges, churn_edges, replace=False)
        rem = np.stack([np.asarray(entry.graph.edge_src)[eidx],
                        entry.graph.indices[eidx]], axis=1)
        add = rng.integers(0, n, size=(churn_edges, 2))
        info = s.update_graph("churn", add_edges=add, remove_edges=rem,
                              reorder="patch")
        patch_walls.append(info["reorder_seconds"])
        mutate_walls.append(info["mutate_seconds"])

    # the full-tier cost the patch tier avoids: one measured LOrder pass
    # over the final mutated graph (the same work `reorder="full"` pays)
    final = entry.graph
    t0 = time.perf_counter()
    lorder(final)
    lorder_seconds = time.perf_counter() - t0

    ref = EngineSession(redecide_min_queries=10**9)
    ref.register(final, graph_id="ref", expected_queries=4096)
    picks = rng.choice(n, size=6, replace=False)
    bit_identical = all(
        np.array_equal(np.asarray(s.submit("churn", "bfs", [int(v)])),
                       np.asarray(ref.submit("ref", "bfs", [int(v)])))
        for v in picks)

    snap = s.metrics().snapshot()["histograms"]
    serve = merge_histogram_snapshots(
        list(snap.get("engine_serve_seconds", {}).values()))
    patch_median = float(np.median(patch_walls))
    speedup = lorder_seconds / max(patch_median, 1e-9)
    tel = s.telemetry()
    out = {
        "num_vertices": n,
        "num_edges_final": final.num_edges,
        "rounds": rounds,
        "churn_edges_per_round": churn_edges,
        "scheme": entry.decision.scheme,
        "registration_reorder_seconds": round(
            tel["graphs"]["churn"]["ledger"]["reorder_seconds"], 6),
        "full_lorder_seconds": round(lorder_seconds, 6),
        "patch_reorder_seconds_median": round(patch_median, 6),
        "patch_reorder_seconds_max": round(float(np.max(patch_walls)), 6),
        "mutate_seconds_median": round(float(np.median(mutate_walls)), 6),
        "patch_speedup_vs_lorder": round(speedup, 1),
        "patch_at_least_10x_cheaper": bool(speedup >= 10.0),
        "serve_p50_ms": round((serve.get("p50") or 0.0) * 1e3, 3),
        "serve_p99_ms": round((serve.get("p99") or 0.0) * 1e3, 3),
        "generations": entry.generation,
        "hot_prefix_len": entry.hot_prefix_len,
        "probe_drift": round(entry.probe_drift, 4),
        "mutations": tel["mutations"],
        "bit_identical": bit_identical,
    }
    s.close(drain=False)
    ref.close(drain=False)
    print(f"[engine] churn: {rounds} rounds x {churn_edges} edges on "
          f"{entry.decision.scheme}; patch {patch_median * 1e3:.1f}ms vs "
          f"LOrder {lorder_seconds:.2f}s ({speedup:.0f}x), serve p99 "
          f"{out['serve_p99_ms']:.1f}ms, bit-identical={bit_identical}",
          flush=True)
    return out


def _phase_knn(scale, bursts: int = 4, queries_per_burst: int = 24):
    """k-NN search serving: recall, latency, and visit-driven reordering.

    A clustered NSW corpus (Zipf cluster sizes) serves a Zipf query mix
    through the request plane. After traffic accumulates,
    ``refresh_hotness`` folds the visit telemetry into the layout (full
    visitsort, then the steady-state patch tier). The locality claim is
    checked with the cache simulator: the per-query visited-vertex
    traces are replayed over the *vector rows* under three layouts —
    identity, degree-ordered (hubsort; structurally blind here, every
    row has out-degree k), and visit-ordered — and the visit-ordered
    layout must show the lowest simulated miss rate. Recall@10 against
    brute force and bit-identity across the reorder are reported too.
    """
    from repro.cache.sim import CacheConfig, simulate_misses
    from repro.core.baselines import hubsort_order, knn_search_baseline
    from repro.core.generators import clustered_vectors
    from repro.engine import EngineSession
    from repro.engine.obs import merge_histogram_snapshots
    from repro.search import (SearchParams, build_nsw_graph,
                              knn_brute_force, medoid_entry, visit_order)

    n = max(700, int(2400 * scale))
    dim, k_out, k_ret, beam = 16, 12, 10, 32
    # spread 0.4: clusters overlap enough for greedy search to stay
    # navigable across them at this dimensionality (recall ~1.0 at beam
    # 32) while each query still touches only ~20% of the corpus — the
    # visit skew the reorder loop needs
    vecs, _ = clustered_vectors(n, dim=dim, num_clusters=8, zipf=1.2,
                                seed=21, spread=0.4)
    g = build_nsw_graph(vecs, k=k_out)
    oracle_entry = medoid_entry(vecs)

    s = EngineSession(redecide_min_queries=10**9, async_full_reorder=False)
    s.register(g, graph_id="knn", vectors=vecs, expected_queries=1024,
               search_params=SearchParams(k_out=k_out, beam_width=beam,
                                          k_return=k_ret))
    entry = s.registry.get("knn")

    def zipf_queries(seed):
        r = np.random.default_rng(seed)
        base = (r.zipf(1.2, size=queries_per_burst) - 1) % n
        return (vecs[base]
                + r.normal(0, 0.02, (queries_per_burst, dim))
                ).astype(np.float32)

    all_q, all_ids = [], []

    def serve_burst(seed):
        q = zipf_queries(seed)
        fut = s.enqueue("knn", "knn", q)
        s.flush("knn")
        all_q.append(q)
        all_ids.append(np.asarray(fut.result()))

    serve_burst(0)
    r1 = s.refresh_hotness("knn")       # telemetry present -> visitsort
    # bit-identity across the reorder: replay burst 0 under the new layout
    replay = np.asarray(s.submit("knn", "knn", all_q[0]))
    reorder_bit_identical = bool(np.array_equal(replay, all_ids[0]))
    for i in range(1, bursts):
        serve_burst(i)
    r2 = s.refresh_hotness("knn")       # steady state -> patch tier

    queries = np.concatenate(all_q)
    served = np.concatenate(all_ids)
    oracle = knn_brute_force(vecs, queries, k_ret)
    recall = float(np.mean([
        len(set(map(int, a)) & set(map(int, b))) / k_ret
        for a, b in zip(served, oracle)]))

    # ---- simulated miss rates per layout -------------------------------
    # trace: visited original ids per query (host mirror of the served
    # kernel), replayed as accesses to a 4-byte per-vertex property
    # array (visit counters / distance caches — 16 vertices per line,
    # where hot-prefix packing creates line sharing; the 64-byte vector
    # rows each fill a whole line, so they are permutation-invariant by
    # construction). Capacity ~70% of the property array keeps the
    # packed hot set resident while cold traffic churns.
    trace = np.concatenate([
        np.nonzero(knn_search_baseline(g, vecs, q, oracle_entry,
                                       beam_width=beam)[1])[0]
        for q in queries])
    cfg = CacheConfig(size_bytes=max(1024, n * 4 * 7 // 10),
                      ways=8, line_bytes=64, prop_bytes=4, sample_rate=1)
    visits = np.zeros(n)
    visits[:len(entry.visit_ewma)] = entry.visit_ewma
    perms = {
        "identity": np.arange(n, dtype=np.int64),
        "degree": hubsort_order(g),
        "visits": visit_order(visits),
    }
    miss = {name: round(simulate_misses(perm[trace], cfg)["miss_rate"], 4)
            for name, perm in perms.items()}

    snap = s.metrics().snapshot()["histograms"]
    serve = merge_histogram_snapshots(
        list(snap.get("engine_serve_seconds", {}).values()))
    tel = s.telemetry()
    out = {
        "num_vectors": n,
        "dim": dim,
        "k_out": k_out,
        "queries": int(len(queries)),
        "recall_at_10": round(recall, 4),
        "recall_ok": bool(recall >= 0.95),
        "reorder_bit_identical": reorder_bit_identical,
        "refresh_first": {k: r1[k] for k in
                          ("tier", "scheme", "hotness_source",
                           "hot_prefix_len")},
        "refresh_steady": {k: r2[k] for k in ("tier", "scheme")},
        "visit_gini": round(entry.probes.visit_gini, 4),
        "visit_hub_fraction": round(entry.probes.visit_hub_fraction, 4),
        "patch_reorders": tel["mutations"]["patch_reorders"],
        "sim_miss_rate": miss,
        "visits_beats_degree": bool(miss["visits"] <= miss["degree"]),
        "serve_p50_ms": round((serve.get("p50") or 0.0) * 1e3, 3),
        "serve_p99_ms": round((serve.get("p99") or 0.0) * 1e3, 3),
        "result_cache": tel["scheduler"]["result_cache"],
    }
    s.close(drain=False)
    print(f"[engine] knn: {n} vectors, recall@10 {recall:.3f}, "
          f"{r1['tier']}/{r1['scheme']} then {r2['tier']}; sim miss "
          f"identity {miss['identity']:.3f} / degree {miss['degree']:.3f}"
          f" / visits {miss['visits']:.3f}, serve p99 "
          f"{out['serve_p99_ms']:.1f}ms", flush=True)
    return out


PHASES = ("decisions", "redecision", "calibration", "bucketing", "sharded",
          "hot_prefix", "fused", "scheduler", "observability", "sustained",
          "churn", "knn")


def parse_phases(value: str | None) -> list[str]:
    if not value:
        return list(PHASES)
    names = [n.strip() for n in value.split(",") if n.strip()]
    unknown = sorted(set(names) - set(PHASES))
    if unknown:
        raise SystemExit(f"unknown phase(s) {', '.join(unknown)}; "
                         f"choose from {', '.join(PHASES)}")
    return names


def run(scale: float = 0.5, batch: int = 8, repeats: int = 5,
        phases: list[str] | None = None) -> list[dict]:
    from repro.core.generators import road_grid
    from repro.engine import EngineSession

    todo = set(phases or PHASES)
    # the calibration replay reads state the earlier phases create (the
    # suite registrations and the "burst" graph's probes)
    if "calibration" in todo:
        todo |= {"decisions", "redecision"}

    session = EngineSession()
    suite = dict(bench_suite(scale))
    side = max(32, int(128 * np.sqrt(scale)))
    suite["road-sim"] = road_grid(side, shortcuts=64, seed=13,
                                  name="road-sim")

    rows = []
    out = {}
    if "decisions" in todo:
        rows = _phase_decisions(session, suite, batch, repeats)
        out["rows"] = rows
    if "redecision" in todo:
        out["redecision"] = _phase_redecision(session, scale)
    if "calibration" in todo:
        out["calibration_flip"] = _phase_calibration_flip(session, suite)
    if "bucketing" in todo:
        out["bucketing"] = _phase_bucketing(scale)
    if "sharded" in todo:
        out["sharded"] = _phase_sharded(scale)
    if "hot_prefix" in todo:
        out["hot_prefix"] = _phase_hot_prefix(scale)
    if "fused" in todo:
        out["fused"] = _phase_fused(scale)
    if "scheduler" in todo:
        out["scheduler"] = _phase_scheduler(scale)
    if "observability" in todo:
        out["observability"] = _phase_observability(scale)
    if "sustained" in todo:
        out["sustained"] = _phase_sustained(scale)
    if "churn" in todo:
        out["churn"] = _phase_churn(scale)
    if "knn" in todo:
        out["knn"] = _phase_knn(scale)

    out["calibration"] = session.policy.calibrator.as_dict()
    out["executor"] = session.executor.telemetry()
    save_json("engine", out)
    return rows


def main(scale: float = 0.5, phases: list[str] | None = None):
    rows = run(scale, phases=phases)
    if rows:
        cols = ["dataset", "scheme", "reorder_seconds", "predicted_gain",
                "realized_gain", "query_seconds_before",
                "query_seconds_after", "wall_break_even_queries"]
        print("\n=== engine policy + amortization ===")
        print(fmt_table(rows, cols))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of: " + ", ".join(PHASES))
    a = ap.parse_args()
    main(a.scale, parse_phases(a.phases))
