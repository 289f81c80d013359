"""Run one benchmark cell on the chip and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, in this order: set XLA up as every entry point does
(`repro.launch.xla_setup.configure()`), refuse to run without a TPU (or
with fewer chips than the cell asks for), make the weights on the device
from the seed and quantize them in the same jitted program, build the
cell's `ServeEngine` behind `AsyncFrontend(overlap=True)`, warm exactly
the shapes the cell's traffic reaches, then drive the traffic for
`--seconds` and drain what was sent.  `setup_s` runs from process start
to the first request.

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` a slice of the window is traced (profiler on) and the
metrics are the cell's per-layer ones, read by `bench/metrics/<name>.py`.

After the window, with the program's state freed, a sample of the
finished requests (drawn from the seed, the longest always in it) goes
through the model family's plain reference (`families/<family>.py`; for
dense models `lib/reference.py`), and the widest gap by
which a served token's logit lies below the reference's best decides
`correct` against the configuration's limit.  `--control` swaps in the
configuration's control (a lower precision) and is meant to fail.

The last line of standard output is the result; the compared numbers
are the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench.lib import catalog, traffic  # noqa: E402

TRACE_S = 4.0               # the traced slice: the window's last 4 s
TRACE_DIR = ROOT / ".bench_trace"
WARM_RID = 1 << 30          # warm-up request ids, clear of the traffic's


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Counters:
    """Snapshots of the program's own counters (public API only)."""

    def __init__(self, eng, fe):
        self.eng, self.fe = eng, fe

    def snap(self) -> dict:
        for _ in range(5):          # the worker thread may be mid-update
            try:
                info = self.eng.cache_info()
                break
            except RuntimeError:
                time.sleep(0.001)
        return {"t": time.perf_counter(),
                "hidden_host_s": self.fe.stats["hidden_host_s"],
                "segments": info["dispatch_sites"]["segment"],
                "prefills": info["dispatch_sites"]["prefill"],
                "generated": self.eng.total_generated,
                "admits": info["methods"]["admits"]["generate"],
                "graphs": info["graphs"],
                "queued": self.eng.n_queued}


class Compiles:
    """Counts programs built (compiled or loaded from the persistent
    cache) and persistent-cache hits, through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.built = self.hits = 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self):
        return self.built, self.hits


class Collections:
    """Garbage collections since it was made, through `gc.callbacks`: how
    many of each generation, and the longest."""

    def __init__(self):
        self.count, self.longest, self._t = [0, 0, 0], 0.0, None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count[info["generation"]] += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)


def build_engine(cfg: dict, fmt: dict, family, mcfg, params):
    """The configuration's `engine` block, but for `lowering` (the
    lowering its GEMM must resolve to), as `ServeEngine` arguments, with
    the family's `engine_kwargs` if it has any."""
    from repro.launch.engine import ServeEngine

    kw = {k: v for k, v in cfg["engine"].items() if k != "lowering"}
    extra = getattr(family, "engine_kwargs", None)
    if extra is not None:
        kw.update(extra(cfg, mcfg, params))
    eng = ServeEngine(params, mcfg, chaos=None, **kw)
    census = eng.cache_info()["lowerings"]
    op = "packed_w4_matmul" if fmt["format"] == "w4a8" else "quant_matmul"
    lowering = cfg["engine"]["lowering"]
    if census.get(op) != lowering:
        raise SystemExit(f"lowering census {census}: {op} is not "
                         f"{lowering}")
    return eng


def warm(eng, mix: dict, reqs) -> int:
    """Compile every shape the traffic reaches: the engine's segment and
    prefill grids for the mix's prompt buckets, then one admission of
    every group size into every prefill cache bucket (the engine scatters
    a group's rows with a program per group size), each request one token
    long so that it ends at admission.  A mix with shared prefixes then
    serves each of the run's prefixes once, so that an engine with a
    prefix cache starts the window with them in its pool."""
    from repro.launch import scheduler

    lens = traffic.prompt_lengths(mix)
    n = eng.warmup(prompt_lens=lens)
    cap, rid = eng.max_cache_len, WARM_RID
    cheapest = {}
    for L in lens:
        sb = scheduler.bucket_pow2(L, eng.min_prompt_bucket, cap)
        t_pre = scheduler.bucket_pow2(sb, eng.min_len_bucket, cap)
        cheapest[t_pre] = min(cheapest.get(t_pre, L), L)
    for L in sorted(cheapest.values()):
        for g in range(1, eng.n_slots + 1):
            group = [scheduler.Request(rid=rid + i,
                                       prompt=np.full(L, 1, np.int32),
                                       max_new_tokens=1) for i in range(g)]
            rid += g
            eng.run(group)
    if "prefix" in mix:
        heads = {r.prompt[:mix["prefix"]["len"]].tobytes(): r for r in reqs}
        eng.run([scheduler.Request(
            rid=rid + i, prompt=r.prompt[:mix["prefix"]["len"]],
            max_new_tokens=1) for i, r in enumerate(heads.values())])
    return n


async def drive(fe, reqs, mix, seconds, n_slots, tracer, counters,
                rid0=0):
    """Send the traffic, measure [w0, w0 + seconds), drain.  Returns
    (records, w0, w1, snapshots, traffic start)."""
    now = time.perf_counter
    recs = [traffic.Record(scheduled=0.0, want=r.max_new_tokens,
                           prompt_len=len(r.prompt)) for r in reqs]
    state = {"first": 0, "w0": None, "stop": False, "snaps": {}}
    started = asyncio.Event()

    async def send(r, rec):
        rec.sent = now()
        try:
            async for tok in fe.generate_stream(r.prompt, r.max_new_tokens,
                                                rid=rid0 + r.index):
                rec.token_times.append(now())
                rec.tokens.append(int(tok))
                if len(rec.tokens) == 1:
                    state["first"] += 1
                    if (mix["loop"] == "closed" and state["w0"] is None
                            and state["first"] >= n_slots):
                        state["w0"] = now()
                        state["snaps"]["w0"] = counters.snap()
                        started.set()
        except Exception as e:  # noqa: BLE001 -- a failed request
            rec.error = repr(e)

    tasks = []
    t_traffic = now()
    if mix["loop"] == "open":
        w0 = t_traffic + 0.01
        state["w0"] = w0

        async def at(r, rec):
            rec.scheduled = w0 + r.at
            await asyncio.sleep(max(0.0, rec.scheduled - now()))
            await send(r, rec)

        state["snaps"]["w0"] = counters.snap()
        tasks = [asyncio.create_task(at(r, rec))
                 for r, rec in zip(reqs, recs)]
    else:
        queue = list(zip(reqs, recs))
        queue.reverse()

        async def client():
            while not state["stop"] and queue:
                r, rec = queue.pop()
                rec.scheduled = now()
                await send(r, rec)

        tasks = [asyncio.create_task(client())
                 for _ in range(mix["clients_per_slot"] * n_slots)]
        await asyncio.wait_for(started.wait(), timeout=120)
    w0 = state["w0"]
    w1 = w0 + seconds
    if tracer is not None:
        # stopping the profiler holds the host for tens of seconds, so
        # the slice ends with the window, after its last snapshot
        await asyncio.sleep(max(0.0, w1 - TRACE_S - now()))
        await tracer.start()
    await asyncio.sleep(max(0.0, w1 - now()))
    state["snaps"]["w1"] = counters.snap()
    state["stop"] = True
    if tracer is not None:
        await tracer.stop()
    done, pending = await asyncio.wait(tasks, timeout=mix["drain_s"])
    for t in pending:
        t.cancel()
    for rec in recs:
        if rec.sent is not None and not rec.done and rec.error is None:
            rec.error = "not finished by the end of the drain"
    return recs, w0, w1, state["snaps"], t_traffic


class Tracer:
    """The profiler, started and stopped off the event loop's thread."""

    async def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: jax.profiler.start_trace(
                str(TRACE_DIR), profiler_options=opts))

    async def stop(self):
        import jax
        await asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace)


def run_traffic(eng, reqs, mix, seconds, n_slots, trace_on, rid0=0):
    from repro.launch.frontend import AsyncFrontend

    async def main():
        fe = AsyncFrontend(eng, overlap=True)
        await fe.start()
        try:
            return fe, await drive(fe, reqs, mix, seconds, n_slots,
                                   Tracer() if trace_on else None,
                                   Counters(eng, fe), rid0)
        finally:
            await fe.stop()

    return asyncio.run(main())


def sweep(args, eng, cfg, mix) -> int:
    """Open-loop windows at each rate on one warm engine: the backlog
    (queued requests) at the window's end, and what the clients saw."""
    rid0 = 0
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate, drain_s=min(mix["drain_s"], 30))
        reqs = traffic.build(m, args.seed, args.seconds, cfg["vocab_size"],
                             cfg["engine"]["n_slots"])
        _, (recs, w0, w1, snaps, _) = run_traffic(
            eng, reqs, m, args.seconds, cfg["engine"]["n_slots"], False,
            rid0)
        rid0 += len(reqs)
        summ = traffic.summarize(recs, w0, w1)
        print(json.dumps({"rate_per_s": rate,
                          "queued_at_end": snaps["w1"]["queued"],
                          **summ}), flush=True)
    return 0


def sample(recs, k: int, seed: int):
    """Up to k finished requests: the longest, and the rest drawn from the
    seed."""
    fin = [i for i, r in enumerate(recs) if r.done]
    if not fin:
        return []
    longest = max(fin, key=lambda i: len(recs[i].tokens))
    rest = [i for i in fin if i != longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[int(j)] for j in sorted(pick)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rates", default=None,
                    help="knee sweep: comma-separated open-loop rates, one "
                         "window of --seconds each after one set-up; prints "
                         "a line per rate and no result")
    ap.add_argument("--control", action="store_true",
                    help="serve the configuration's control (a lower "
                         "precision); the comparison should fail")
    args = ap.parse_args(argv)
    cat = catalog.Catalog(ROOT)
    cell = cat.workload(args.workload)
    cfg = cat.config(cell["config"])
    mix = cat.traffic(cell["traffic"])
    try:
        from repro.launch import xla_setup
    except ImportError as e:
        log(f"bench: cannot import the program: {e}")
        return 2
    setup = xla_setup.configure()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devs)} {devs[0].platform} device(s)")
        return 1
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; compile "
        f"cache {setup.cache_dir}; exact rounding {setup.exact_rounding}")
    return serve_cell(args, cat, cell, cfg, mix, dev, len(devs))


def serve_cell(args, cat, cell, cfg, mix, dev, n_dev) -> int:
    import jax

    from bench.lib import peaks
    peak = peaks.peaks(dev.device_kind)
    compiles = Compiles()
    control = cfg["correct"]["control"] if args.control else None
    fmt = dict(cfg["weights"])
    if control and control["kind"] == "program_format":
        fmt.update(control["weights"])
    family = cat.family(cfg["program"]["family"])
    t = time.perf_counter()
    params = family.make_params(cfg, fmt, args.seed)
    log(f"weights made and quantized ({fmt['format']}): "
        f"{time.perf_counter() - t:.3f} s")
    mcfg = family.model_config(cfg)
    eng = build_engine(cfg, fmt, family, mcfg, params)
    reqs = traffic.build(mix, args.seed, args.seconds, cfg["vocab_size"],
                         cfg["engine"]["n_slots"])
    t = time.perf_counter()
    graphs = warm(eng, mix, reqs)
    log(f"warm-up: {graphs} engine graphs, {time.perf_counter() - t:.3f} s;"
        f" engine census {eng.cache_info()['lowerings']}")
    # what set-up made lives as long as the process: out of the
    # collector's reach, so that a full collection in the window walks
    # only what the window made
    gc.collect()
    gc.freeze()
    collections = Collections()
    if args.rates:
        return sweep(args, eng, cfg, mix)
    built0 = compiles.snap()
    fe, (recs, w0, w1, snaps, t_traffic) = run_traffic(
        eng, reqs, mix, args.seconds, cfg["engine"]["n_slots"],
        bool(args.trace))
    built1 = compiles.snap()
    setup_s = t_traffic - T_START
    stats = dev.memory_stats() or {}
    summ = traffic.summarize(recs, w0, w1)
    c0, c1 = snaps["w0"], snaps["w1"]
    delta = {k: c1[k] - c0[k] for k in c0}
    log(f"window: {w1 - w0:.3f} s, {summ['attempted']} requests sent, "
        f"{summ['failed']} failed, {summ['window_tokens']} tokens; "
        f"sender late p95 {summ['sender_late_p95_ms']} ms, max "
        f"{summ['sender_late_max_ms']} ms")
    log(f"programs built inside the window: {built1[0] - built0[0]} "
        f"({built1[1] - built0[1]} from the persistent cache); engine "
        f"graphs added: {delta['graphs']}")
    log(f"garbage collections in the window and its drain: "
        f"{collections.count} by generation, longest "
        f"{1e3 * collections.longest:.1f} ms")
    log(f"counters over the window ({delta.pop('t'):.3f} s between "
        f"snapshots): {json.dumps(delta)}")
    log(f"frontend: {json.dumps(fe.stats)}")
    info = eng.cache_info()
    log("engine: " + json.dumps({k: info[k] for k in (
        "graphs", "graph_bound", "batch_buckets", "len_buckets",
        "robustness", "dispatch_sites", "first_error", "prefix_cache")
        if k in info}))
    log("memory: " + json.dumps({k: stats.get(k) for k in (
        "peak_bytes_in_use", "bytes_limit", "bytes_in_use")}))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev,
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    ctx = Context(cfg=cfg, fmt=fmt, family=family, mix=mix, peak=peak,
                  records=recs, summary=summ, w0=w0, w1=w1, delta=delta,
                  memory=stats, trace=None)
    breakdown = None
    if args.trace:
        from bench.lib import trace as trace_mod
        t = time.perf_counter()
        red = trace_mod.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx.trace = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        log(f"trace reduced in {time.perf_counter() - t:.3f} s: "
            f"{red.n_events} device events, busy {red.busy_s} s of "
            f"{red.window_s} s, idle gaps {red.idle_s} s")
        log(f"idle share by second of the slice: "
            f"{json.dumps(red.idle_by_s)}")
        log(f"longest idle gaps by host event: "
            f"{json.dumps(red.gaps_by_host()[:15])}")

    # the reference runs with the program's state freed
    chosen = sample(recs, cfg["correct"]["sample"], args.seed)
    seqs = [(reqs[i].prompt, np.asarray(recs[i].tokens, np.int32))
            for i in chosen]
    fe.engine = None
    del fe, eng, params
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"program state freed: {live} bytes live")
    t = time.perf_counter()
    act = control["act_bits"] if control and \
        control["kind"] == "reference_precision" else None
    gaps = family.served_gaps(cfg, cfg["weights"], args.seed, seqs,
                              control_act_bits=act) if seqs else []
    worst = float(max(float(g.max()) for g in gaps)) if seqs else None
    limit = cfg["correct"]["max_logit_gap"]
    correct = worst is not None and worst <= limit
    log(f"reference over {len(seqs)} requests, "
        f"{sum(len(s[1]) for s in seqs)} served tokens: "
        f"{time.perf_counter() - t:.3f} s")

    if args.trace:
        metrics = {}
        for m in cat.metrics(args.workload, "per_layer"):
            v = cat.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(summ, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cat.metrics(args.workload, "end_to_end")}
    compared = {"max_logit_gap": {"value": worst, "limit": limit},
                "requests_compared": {"value": len(seqs),
                                      "limit": cfg["correct"]["sample"]}}
    result = {"correct": bool(correct), "attempted": summ["attempted"],
              "failed": summ["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    log(f"all metrics: {json.dumps(dict(summ, setup_s=setup_s))}")
    log(f"compared: max_logit_gap {worst} (limit {limit})")
    log(f"compared: requests_compared {len(seqs)} (of "
        f"{cfg['correct']['sample']} asked)")
    print(json.dumps(result), flush=True)
    return 0


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets: the configuration, the weight
    format served, the model family's module, the mix, the chip's peaks,
    the client records and their `traffic.summarize`, the window [w0,
    w1), the program's counters over it (`delta`), memory stats after the
    window and the trace reduction (None without a trace)."""
    cfg: dict
    fmt: dict
    family: object
    mix: dict
    peak: dict
    records: list
    summary: dict
    w0: float
    w1: float
    delta: dict
    memory: dict
    trace: object


if __name__ == "__main__":
    sys.exit(main())
