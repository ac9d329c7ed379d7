#!/usr/bin/env python3
"""Bring-up smoke of the RLC serving path on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --chips 4       # sharded serving over four chips

One chip: build the index of a seeded Soc-Epinions-shaped graph on the
host, freeze it, put its padded layout on the chip, and answer a Zipf mix
of true and false ``(s, t, L+)`` queries through ``RLCService`` — first
with the backend ``auto`` resolves to (the Pallas merge-join kernel),
then with the XLA sorted join. Every answer is checked against the host
CSR join, and a sample against the BiBFS oracle. Then one small
``apply_delta`` and the same checks, and a device (``pallas``) index
build of a smaller graph, entry for entry against the host build.

``--chips 4``: the same graph served by ``ShardedRLCService`` with one
shard on each chip, against a one-chip ``RLCService`` and the host join
on the same queries, cross-shard queries included.

Fails (exit 1, no result line) when JAX finds no TPU, when an executor
falls back, when an answer disagrees, when a kernel would run in
interpret mode, or when any phase raises. The last line of a passing run
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

#: Soc-Epinions (SNAP): 75,879 vertices, 508,837 edges, the paper's EP
#: graph. Shape as the repo's EP stand-in (``benchmarks/common.py``): BA
#: with m = 3 attachments (its avg degree 6.8 halved; with the generator's
#: reverse edges, 4.5 edges per vertex against the published 6.7),
#: |L| = 8 Zipf-labelled, k = 2. Vertex count cut to 16,384 by the host
#: build's time, which grows faster than linear in V.
PUBLISHED_VERTICES = 75_879
VERTICES = 16_384
BA_M = 3
NUM_LABELS = 8
K = 2
#: the device build holds two dense (|L|, Vp, Vp) f32 adjacencies, which
#: fit one chip only well below the served graph's size
BUILD_VERTICES = 2_048
#: hubs whose two-hop estimate reaches this run their waves on the
#: device (the default, 2,000, sends one hub of this graph there)
BUILD_GATHER_THRESHOLD = 256
BATCH = 128             # scheduler batch; the executor pads to pow2
POOL = 2_048            # distinct queries: half walk-true, half uniform
REQUESTS = 4_096        # Zipf-drawn from the pool
ORACLE_SAMPLE = 48      # BiBFS checks per phase (half true, half false)
DELTA_EDGES = 2         # edges inserted and deleted by the delta phase


class SmokeError(AssertionError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------- #
# Workload
# --------------------------------------------------------------------- #
def make_graph(n: int, seed: int):
    from repro.graphgen import barabasi_albert
    return barabasi_albert(n, BA_M, NUM_LABELS, seed=seed)


def make_requests(g, seed: int, pool: int = POOL,
                  requests: int = REQUESTS):
    """Zipf-popular requests over a pool of walk-true and uniform (mostly
    false) queries; returns ``[(s, t, L)]``."""
    import numpy as np
    from repro.core.queries import biased_true_queries
    rng = np.random.default_rng(seed)
    true_q = biased_true_queries(g, K, pool // 2, seed=seed,
                                 n_false=0).true_queries
    mrs = sorted({L for _, _, L in true_q})
    uni = [(int(rng.integers(g.num_vertices)),
            int(rng.integers(g.num_vertices)),
            mrs[int(rng.integers(len(mrs)))])
           for _ in range(pool - len(true_q))]
    qs = true_q + uni
    order = rng.permutation(len(qs))
    p = 1.0 / np.arange(1, len(qs) + 1)
    draws = rng.choice(len(qs), size=requests, p=p / p.sum())
    return [qs[int(order[d])] for d in draws]


# --------------------------------------------------------------------- #
# Checks
# --------------------------------------------------------------------- #
def require_kernel(fn, *args, **static) -> None:
    """The lowered program holds a compiled Mosaic kernel, not the
    Pallas interpreter's loop."""
    text = fn.lower(*args, **static).as_text()
    check("tpu_custom_call" in text,
          f"{getattr(fn, '__name__', fn)} lowers without a TPU kernel "
          "(interpret mode)")


def host_answers(frozen, mr_ids, reqs):
    """The host CSR merge join (numpy) on the same requests."""
    import numpy as np
    s = np.array([q[0] for q in reqs], np.int64)
    t = np.array([q[1] for q in reqs], np.int64)
    m = np.array([mr_ids[tuple(q[2])] for q in reqs], np.int64)
    return np.asarray(frozen.query_batch(s, t, m), dtype=bool)


def check_answers(tag, answers, want, reqs, graph, seed) -> dict:
    """All answers equal the host join; a sample equals BiBFS."""
    import numpy as np
    from repro.core.baselines import bibfs_rlc
    got = np.array([bool(a) for a in answers])
    bad = np.flatnonzero(got != want)
    check(bad.size == 0, f"{tag}: {bad.size} answers differ from the host "
          f"join, first {[reqs[i] for i in bad[:4]]}")
    rng = np.random.default_rng(seed)
    picks = []
    for val in (True, False):
        idx = np.flatnonzero(want == val)
        picks += rng.choice(idx, size=min(ORACLE_SAMPLE // 2, idx.size),
                            replace=False).tolist()
    check(len(picks) >= 32, f"{tag}: only {len(picks)} oracle samples")
    t0 = time.perf_counter()
    for i in picks:
        s, t, L = reqs[i]
        check(bibfs_rlc(graph, s, t, L) == bool(want[i]),
              f"{tag}: BiBFS disagrees on {(s, t, L)}")
    return dict(n=len(reqs), true=int(want.sum()), oracle=len(picks),
                oracle_s=time.perf_counter() - t0)


def backends_of(answers) -> dict:
    out: dict = {}
    for a in answers:
        out[a.backend] = out.get(a.backend, 0) + 1
    return out


def warm_shapes(executor, reqs, mr_ids, backend: str) -> dict:
    """Run every pow2 batch shape the executor can see once, twice:
    the first call's extra time is the compile (or cache load)."""
    import numpy as np
    s = np.array([q[0] for q in reqs[:BATCH]], np.int32)
    t = np.array([q[1] for q in reqs[:BATCH]], np.int32)
    m = np.array([mr_ids[tuple(q[2])] for q in reqs[:BATCH]], np.int32)
    out = {}
    cap = 1
    while cap <= BATCH:
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            _, b = executor.execute(s[:cap], t[:cap], m[:cap],
                                    backend=backend)
            times.append(time.perf_counter() - t0)
        check(b == backend, f"warm-up of {backend} answered by {b}")
        out[cap] = times[0] - times[1]
        cap *= 2
    return out


def serve(svc, reqs, tag: str, backend: str, seed: int) -> dict:
    svc.executor.backend = backend
    fb0 = svc.executor.fallbacks
    compile_s = warm_shapes(svc.executor, reqs, svc.mr_ids,
                            svc.executor.resolve())
    t0 = time.perf_counter()
    answers = svc.query_batch(reqs)
    wall = time.perf_counter() - t0
    want = host_answers(svc.frozen, svc.mr_ids, reqs)
    res = check_answers(tag, answers, want, reqs, svc.graph, seed)
    used = backends_of(answers)
    check(svc.executor.fallbacks == fb0,
          f"{tag}: {svc.executor.fallbacks - fb0} executor fallbacks")
    res.update(backends=used, serve_s=wall,
               compile_s={str(k): v for k, v in compile_s.items()},
               compile_total_s=sum(compile_s.values()))
    log(f"[{tag}] answered by {used}; {res['n']} requests "
        f"({res['true']} true) equal the host join; {res['oracle']} equal "
        f"BiBFS; 0 fallbacks; serve {wall:.3f} s")
    log(f"[{tag}] first-call compile s per batch shape: "
        + ", ".join(f"Q={k}: {v:.3f}" for k, v in compile_s.items())
        + f" (total {res['compile_total_s']:.3f})")
    return res


def device_bytes(dev) -> int:
    return sum(int(a.nbytes) for a in (dev.out_hub, dev.out_mr, dev.in_hub,
                                        dev.in_mr, dev.out_key, dev.in_key))


# --------------------------------------------------------------------- #
# One chip
# --------------------------------------------------------------------- #
def one_chip(seed: int, vertices: int = VERTICES,
             build_vertices: int = BUILD_VERTICES) -> dict:
    import jax
    import numpy as np
    from repro.graphgen import random_delta
    from repro.kernels import ops
    from repro.service import RLCService, ServiceConfig

    t0 = time.perf_counter()
    g = make_graph(vertices, seed)
    log(f"[graph] BA m={BA_M} |L|={NUM_LABELS} k={K}: V={g.num_vertices} "
        f"(cut from {PUBLISHED_VERTICES}), edges={g.num_edges} "
        f"({g.num_edges / g.num_vertices:.2f} per vertex), "
        f"generated in {time.perf_counter() - t0:.1f} s")
    svc = RLCService.build(g, ServiceConfig(
        k=K, batch_size=BATCH, cache_capacity=0, backend="auto",
        build_backend="auto"))
    dev = svc.device_index
    t0 = time.perf_counter()
    jax.block_until_ready([dev.out_hub, dev.out_mr, dev.in_hub, dev.in_mr,
                           dev.out_key, dev.in_key])
    ready = time.perf_counter() - t0
    log(f"[index] entries={svc.index.num_entries()} "
        f"max_row={svc.frozen.max_row} row_len={dev.row_len} "
        f"device_bytes={device_bytes(dev)} on "
        f"{sorted(str(d) for d in dev.out_hub.devices())}")
    log(f"[index] build {svc.build_stats.wall_time_s:.3f} s "
        f"({svc.build_stats.backend}), freeze "
        f"{svc.setup_seconds['freeze']:.3f} s, device layout "
        f"{svc.setup_seconds['device']:.3f} s + transfer wait {ready:.3f} s")
    reqs = make_requests(g, seed)
    s = np.array([q[0] for q in reqs[:8]], np.int32)
    m = np.array([svc.mr_ids[tuple(q[2])] for q in reqs[:8]], np.int32)
    require_kernel(ops.mergejoin_query, dev.out_hub, dev.out_mr,
                   dev.in_hub, dev.in_mr, s, s, m)
    check(svc.executor.resolve() == "pallas",
          f"auto resolves to {svc.executor.resolve()}, not pallas")

    out = dict(pallas=serve(svc, reqs, "serve:auto", "auto", seed))
    check(out["pallas"]["backends"] == {"pallas": len(reqs)},
          f"auto served by {out['pallas']['backends']}")
    out["sorted"] = serve(svc, reqs, "serve:sorted", "sorted", seed + 1)
    check(out["sorted"]["backends"] == {"sorted": len(reqs)},
          f"sorted served by {out['sorted']['backends']}")

    delta = random_delta(g, DELTA_EDGES, DELTA_EDGES,
                         np.random.default_rng(seed + 2))
    t0 = time.perf_counter()
    summary = svc.apply_delta(delta)
    log(f"[delta] +{delta.inserts.shape[0]} -{delta.deletes.shape[0]} "
        f"edges in {time.perf_counter() - t0:.3f} s (incl. the delta "
        f"builder's first full build); fallback="
        f"{summary['delta']['fallback']} row_len={svc.device_index.row_len}")
    out["delta"] = serve(svc, reqs, "delta:auto", "auto", seed + 3)
    check(out["delta"]["backends"] == {"pallas": len(reqs)},
          f"post-delta served by {out['delta']['backends']}")
    out["device_build"] = device_build(seed, build_vertices)
    return out


def device_build(seed: int, vertices: int) -> dict:
    """``build_backend="pallas"`` at a size whose dense adjacency fits,
    entry for entry against the host (numpy) build."""
    from repro.build import build_rlc_index_with_stats, get_backend
    from repro.kernels import ops
    import numpy as np

    g = make_graph(vertices, seed + 7)
    ref, ref_st = build_rlc_index_with_stats(g, K, backend="numpy")
    backend = get_backend("pallas", gather_threshold=BUILD_GATHER_THRESHOLD)
    idx, st = backend.build(g, K)
    eng = backend.engine
    check(not eng.interpret, "device build ran in interpret mode")
    check(eng.waves > 0, "device build ran no wave on the device")
    Rp, Vp = min(eng.shapes)
    require_kernel(ops.frontier_wave_packed,
                   np.zeros((Rp, Vp), np.float32), eng._A[0],
                   np.zeros(Rp, np.int32), interpret=False)
    check(idx.l_out == ref.l_out and idx.l_in == ref.l_in,
          "device build entries differ from the host build")
    check(st.counters() == ref_st.counters(),
          "device build counters differ from the host build")
    log(f"[device build] V={vertices} edges={g.num_edges} "
        f"entries={idx.num_entries()} equal to numpy; {eng.waves} device "
        f"waves over {len(eng.shapes)} shapes; pallas "
        f"{st.wall_time_s:.3f} s vs numpy {ref_st.wall_time_s:.3f} s")
    return dict(vertices=vertices, entries=idx.num_entries(),
                waves=eng.waves, pallas_s=st.wall_time_s,
                numpy_s=ref_st.wall_time_s)


# --------------------------------------------------------------------- #
# Four chips
# --------------------------------------------------------------------- #
def four_chips(seed: int, vertices: int = VERTICES) -> dict:
    import jax
    from repro.service import (RLCService, ServiceConfig,
                               ShardedRLCService, ShardedServiceConfig)

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX has "
          f"{len(devices)}")
    g = make_graph(vertices, seed)
    log(f"[graph] BA m={BA_M} |L|={NUM_LABELS} k={K}: V={g.num_vertices} "
        f"(cut from {PUBLISHED_VERTICES}), edges={g.num_edges}")
    sharded = ShardedRLCService.build(g, ShardedServiceConfig(
        k=K, batch_size=BATCH, cache_capacity=0, num_shards=4,
        num_replicas=1, transport="inproc", backend="auto",
        build_backend="auto"))
    log(f"[index] entries={sharded.index.num_entries()} build "
        f"{sharded.build_stats.wall_time_s:.3f} s; shard ranges "
        f"{sharded.plan.ranges()}")
    placed = []
    for rs in sharded.shards:
        dev = rs.replicas[0].device_index
        where = {d for a in (dev.out_hub, dev.out_mr, dev.in_hub,
                             dev.in_mr, dev.out_key, dev.in_key)
                 for d in a.devices()}
        check(len(where) == 1, f"shard {rs.shard_id} spans {where}")
        placed.append(where.pop())
        log(f"[shard {rs.shard_id}] rows [{rs.lo}, {rs.hi}) row_len="
            f"{dev.row_len} device_bytes={device_bytes(dev)} on "
            f"{placed[-1]}")
    check(len(set(placed)) == 4, f"shards share devices: {placed}")
    single = RLCService.build(g, ServiceConfig(
        k=K, batch_size=BATCH, cache_capacity=0), index=sharded.index)
    reqs = make_requests(g, seed)
    want = host_answers(sharded.frozen, sharded.mr_ids, reqs)
    t0 = time.perf_counter()
    got = sharded.query_batch(reqs)
    wall = time.perf_counter() - t0
    res = check_answers("sharded", got, want, reqs, g, seed)
    one = single.query_batch(reqs)
    check([bool(a) for a in one] == [bool(a) for a in got],
          "sharded answers differ from the one-chip service")
    check(backends_of(one) == {"pallas": len(reqs)},
          f"one-chip service answered by {backends_of(one)}")
    st = sharded.stats()
    ex = st["executor"]
    fallbacks = sum(sh["fallbacks"] for sh in st["shards"])
    check(fallbacks == 0, f"{fallbacks} executor fallbacks in the shards")
    check(ex["remote_joins_numpy"] == 0 and ex["degraded"] == 0,
          f"cross-shard joins left the device: {ex}")
    check(st["router"]["remote"] > 0 and ex["remote_joins_device"] > 0,
          "no cross-shard query was served")
    used = backends_of(got)
    log(f"[sharded] answered by {used}; router local="
        f"{st['router']['local']} remote={st['router']['remote']}; "
        f"device digest joins={ex['remote_joins_device']} "
        f"digest_bytes={ex['digest_bytes']}; {res['n']} requests "
        f"({res['true']} true) equal the host join and the one-chip "
        f"service; {res['oracle']} equal BiBFS; 0 fallbacks; serve "
        f"{wall:.3f} s (compiles included)")
    return dict(backends=used, serve_s=wall, router=st["router"], **res)


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    src = Path(__file__).resolve().parent / "src"
    try:
        check((src / "repro").is_dir(),
              f"{src} holds no repro package: run from a checkout")
        sys.path.insert(0, str(src))
        import jax
        from repro.device import enable_compile_cache
        cache = enable_compile_cache()
        dev = jax.devices()[0]
        check(dev.platform == "tpu",
              f"JAX found no TPU (platform {dev.platform!r})")
        log(f"[device] {dev.platform} {dev.device_kind} x "
            f"{len(jax.devices())}; compile cache {cache}")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
        log(f"[done] {time.perf_counter() - t0:.1f} s")
    except Exception:  # noqa: BLE001 — every failure exits non-zero
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
