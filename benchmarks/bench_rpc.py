"""Multi-host RPC serving benchmark: local vs loopback vs socket.

Two questions the transport layer must answer:

1. Is the remote path the local path? Bitwise equality of embeddings
   over the first 20 batches is asserted on EVERY run across all four
   deployments — that is the CI rpc-smoke gate.
2. How much of the host<->host hop does the staged pipeline hide? On a
   single machine the loopback RTT is ~0, so the hop is isolated by
   running the SAME socket deployment twice: once plain, once against a
   graph host injecting a known link RTT per call (``--delay-ms``, a
   GIL-releasing sleep). The CPU work is identical on both sides of the
   subtraction, so

       added_closed = closed_loop(rtt) - closed_loop(plain)   ~ RTT
       added_piped  = pipelined(rtt)  - pipelined(plain)

   and the overlap recovery ``1 - added_piped / added_closed`` is the
   fraction of the hop the remote stage's concurrent in-flight calls
   hide under pipelined traffic. Acceptance bar: >= 50%.

Deployments of the same (graph, model, params):

  local        Select/Build in-process (the baseline)
  inproc       loopback transport — full wire codec, one process
  socket       graph host SUBPROCESS over TCP, zero injected RTT
  socket+rtt   same, with the simulated link RTT per call

Appends ``results/BENCH_rpc.json``.

``--trace out.json`` additionally runs a TRACED socket deployment
against a live graph-host subprocess and exports a Perfetto-loadable
chrome trace: the graph host's remote.select/remote.build spans are
stitched (after ping-based clock-offset correction) INSIDE the client's
select_build rpc span — the two-process timeline the paper's Fig. 7
overlap claim needs. The run asserts bitwise equality vs local, zero
chrome-trace validation problems, and zero containment violations.

    python benchmarks/bench_rpc.py [--smoke] [--requests N] [--rtt-ms R]
    python benchmarks/bench_rpc.py --trace results/trace.json
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from benchmarks.common import enable_cache, print_table, record_trajectory
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph, zipf_traffic
from repro.store import StorePolicy

BITWISE_BATCHES = 20


def spawn_graph_host(dataset: str, scale: float, seed: int,
                     num_threads: int = 2, delay_ms: float = 0.0):
    """Launch a graph-host subprocess on an ephemeral port; the child
    rebuilds the identical synthetic graph from (dataset, scale, seed)."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.distributed.graph_host",
         "--dataset", dataset, "--scale", str(scale),
         "--seed", str(seed), "--port", "0",
         "--num-threads", str(num_threads),
         "--delay-ms", str(delay_ms)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    t0 = time.time()
    while True:
        line = proc.stdout.readline()
        if line.startswith("GRAPH_HOST_LISTENING"):
            _, host, port = line.split()
            return proc, f"{host}:{port}"
        if proc.poll() is not None or time.time() - t0 > 120:
            proc.kill()
            raise RuntimeError(f"graph host failed to start: {line!r}")


def measure(eng, traffic: np.ndarray, c: int, pipelined: bool) -> dict:
    """Drive one engine over the traffic stream. pipelined=False keeps
    one batch in flight (closed loop — every batch pays the full hop);
    pipelined=True submits everything and lets the scheduler overlap
    stations and in-flight remote calls."""
    chunks = [traffic[i:i + c] for i in range(0, len(traffic) - c + 1, c)]
    s = eng.scheduler.stats
    base_wall = s.t_rpc_wall
    t0 = time.perf_counter()
    if pipelined:
        for t in [eng.submit_chunk(ch) for ch in chunks]:
            t.result(timeout=600)
    else:
        for ch in chunks:
            eng.submit_chunk(ch).result(timeout=600)
    wall = time.perf_counter() - t0
    return {"batches": len(chunks),
            "batch_ms": wall / len(chunks) * 1e3,
            "req_per_s": len(chunks) * c / wall,
            "rpc_wall_ms": (s.t_rpc_wall - base_wall)
            / len(chunks) * 1e3}


def run(requests: int = 2048, batch_size: int = 8, scale: float = 0.01,
        receptive_field: int = 32, zipf_a: float = 1.1, seed: int = 0,
        rtt_ms: float = 5.0, dataset: str = "flickr") -> dict:
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph(dataset, scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    traffic = zipf_traffic(g, requests, zipf_a, seed + 1)
    warm = traffic[:max(batch_size * 8, len(traffic) // 4)]
    meas = traffic[len(warm):]
    check = np.concatenate(
        [traffic[i:i + batch_size] for i in
         range(0, BITWISE_BATCHES * batch_size, batch_size)])
    print(f"graph: V={g.num_vertices} f={g.feature_dim} | "
          f"Zipf({zipf_a}) {requests} requests ({len(warm)} warmup), "
          f"C={batch_size} N={receptive_field} | simulated link RTT "
          f"{rtt_ms}ms")

    store = StorePolicy(features="resident", nbr_cache="lru",
                        nbr_capacity=1024)
    base = ServingConfig(batch_size=batch_size, num_threads=2,
                         store=store, rpc_timeout_s=300.0)
    hosts = {
        "socket": spawn_graph_host(dataset, scale, seed),
        "socket+rtt": spawn_graph_host(dataset, scale, seed,
                                       delay_ms=rtt_ms),
    }
    configs = {
        "local": base,
        "inproc": dataclasses.replace(base, transport="inproc"),
        **{name: dataclasses.replace(base, transport="socket",
                                     endpoints=(ep,))
           for name, (_, ep) in hosts.items()},
    }
    rows, refs, rpc_stats = [], {}, {}
    try:
        for name, sc in configs.items():
            with DecoupledEngine(g, cfg, params=params,
                                 config=sc) as eng:
                refs[name] = eng.infer(check, overlap=False).embeddings
                for ch in range(0, len(warm) - batch_size + 1,
                                batch_size):          # compile + caches
                    eng.submit_chunk(
                        warm[ch:ch + batch_size]).result(timeout=600)
                closed = measure(eng, meas, batch_size, pipelined=False)
                piped = measure(eng, meas, batch_size, pipelined=True)
                row = {"deployment": name,
                       "closed_ms": round(closed["batch_ms"], 3),
                       "piped_ms": round(piped["batch_ms"], 3),
                       "req_per_s": round(piped["req_per_s"], 1),
                       "rpc_wall_ms": round(closed["rpc_wall_ms"], 3)}
                s = eng.scheduler.stats
                if s.rpc_calls:
                    rpc_stats[name] = s.summary()["rpc"]
                    row["kb_out"] = round(
                        s.rpc_bytes_out / s.rpc_calls / 1024, 1)
                    row["kb_in"] = round(
                        s.rpc_bytes_in / s.rpc_calls / 1024, 1)
                rows.append(row)
                print(f"  [{name}] closed={row['closed_ms']}ms "
                      f"piped={row['piped_ms']}ms "
                      f"({row['req_per_s']} req/s)", flush=True)
    finally:
        for proc, _ in hosts.values():
            proc.kill()
            proc.wait(timeout=10)

    # the CI gate: the remote path IS the local path, bitwise, over
    # every transport (loopback, TCP, TCP behind a slow link)
    for name in ("inproc", "socket", "socket+rtt"):
        np.testing.assert_array_equal(refs[name], refs["local"])
    print(f"bitwise: all deployments == local over "
          f"{BITWISE_BATCHES} batches OK")

    # hop-hiding: same deployment, same CPU work — the only difference
    # between socket and socket+rtt is the known injected RTT
    by = {r["deployment"]: r for r in rows}
    added_closed = by["socket+rtt"]["closed_ms"] - by["socket"]["closed_ms"]
    added_piped = by["socket+rtt"]["piped_ms"] - by["socket"]["piped_ms"]
    recovery = 1.0 - max(0.0, added_piped) / max(added_closed, 1e-9)
    print(f"added hop latency ({rtt_ms}ms RTT): closed-loop "
          f"+{added_closed:.3f}ms/batch, pipelined "
          f"+{added_piped:.3f}ms/batch -> overlap hides {recovery:.0%}")
    assert recovery >= 0.5, (
        f"pipelining hides only {recovery:.0%} of the hop "
        f"(closed +{added_closed:.3f}ms vs piped +{added_piped:.3f}ms); "
        "acceptance bar is 50%")

    print()
    print_table(rows, ["deployment", "closed_ms", "piped_ms",
                       "req_per_s", "rpc_wall_ms", "kb_out", "kb_in"])
    payload = {"rows": rows, "overlap_recovery": round(recovery, 3),
               "rtt_ms": rtt_ms,
               "added_closed_ms": round(added_closed, 3),
               "added_piped_ms": round(added_piped, 3),
               "rpc": rpc_stats, "requests": requests,
               "batch_size": batch_size,
               "receptive_field": receptive_field,
               "bitwise_batches": BITWISE_BATCHES,
               "num_vertices": g.num_vertices, "zipf_a": zipf_a}
    record_trajectory("rpc", payload)
    return payload


def run_traced(out_path: str = "results/trace.json",
               requests: int = 64, batch_size: int = 8,
               scale: float = 0.004, receptive_field: int = 16,
               seed: int = 0, dataset: str = "flickr") -> dict:
    """Two-process traced run: device host here, graph host in a
    subprocess over TCP. Exports the stitched chrome trace to
    ``out_path`` and gates on bitwise equality, trace validity, and
    remote-span containment."""
    import jax

    from repro.gnn.model import init_gnn
    from repro.obs import TraceConfig, containment, validate_chrome_trace

    g = get_graph(dataset, scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    traffic = zipf_traffic(g, requests, 1.1, seed + 1)
    store = StorePolicy(features="resident", nbr_cache="lru",
                        nbr_capacity=1024)
    base = ServingConfig(batch_size=batch_size, num_threads=2,
                         store=store, rpc_timeout_s=300.0)
    with DecoupledEngine(g, cfg, params=params, config=base) as eng:
        ref = eng.infer(traffic, overlap=False).embeddings
    proc, ep = spawn_graph_host(dataset, scale, seed)
    try:
        sc = dataclasses.replace(base, transport="socket",
                                 endpoints=(ep,),
                                 trace=TraceConfig())
        with DecoupledEngine(g, cfg, params=params, config=sc) as eng:
            out = eng.infer(traffic).embeddings
            spans = eng.tracer.export_spans()
            rep = eng.trace_report()
            tree = eng.export_trace(out_path)
    finally:
        proc.kill()
        proc.wait(timeout=10)
    np.testing.assert_array_equal(ref, out)
    remote = [s for s in spans if s["host"].startswith("graph-host")]
    assert remote, "no remote spans stitched from the graph host"
    problems = validate_chrome_trace(tree)
    assert problems == [], f"chrome trace invalid: {problems[:5]}"
    violations = containment(spans, "select_build", remote[0]["host"])
    assert violations == [], (
        f"remote spans escape their rpc span after clock correction: "
        f"{violations[:3]}")
    sync = rep["clock_sync"][ep]
    print(f"traced socket run: {rep['tickets_traced']} batches, "
          f"{rep['spans']} spans ({len(remote)} remote from {ep}, "
          f"offset {sync['offset_s'] * 1e3:+.3f}ms "
          f"rtt {sync['rtt_s'] * 1e3:.3f}ms)")
    print(f"bitwise vs local OK; containment OK; chrome trace valid "
          f"-> {out_path} (open in https://ui.perfetto.dev)")
    return {"trace_path": out_path, "spans": rep["spans"],
            "remote_spans": len(remote),
            "tickets_traced": rep["tickets_traced"],
            "clock_sync": sync}


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI rpc-smoke shape). Both
    shapes finish with the traced two-process run: CI uploads the
    exported results/trace.json as an artifact."""
    if quick:
        payload = run(requests=512, batch_size=8, scale=0.004,
                      receptive_field=16)
    else:
        payload = run()
    payload["trace"] = run_traced()
    return payload


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--rtt-ms", type=float, default=5.0,
                    help="simulated link RTT injected at the graph host")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI rpc-smoke gate)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="ONLY run the traced two-process socket "
                         "deployment and export the stitched chrome "
                         "trace to PATH")
    a = ap.parse_args()
    if a.trace:
        run_traced(out_path=a.trace)
    elif a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size, zipf_a=a.zipf,
            rtt_ms=a.rtt_ms)
