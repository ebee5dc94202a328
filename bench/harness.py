"""One run of one benchmark cell: set-up, a timed window, the check.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json`` names a configuration (``bench/configs/<config>.json``,
whose layer family is a module of ``bench/families/``) and a traffic mix
(``bench/traffic/<mix>.json``, whose arrival process is a module of
``bench/arrivals/``); each per-layer metric is read by
``bench/metrics/<name before the first dot>.py``.  Adding a cell, a mix, a
metric or a configuration of a new family adds files and entries and edits
none.

The window drives ``EventLoopEngine.step()`` over an ``Overlay`` exactly as
a server would; the generator runs in the same process and, before every
tick, submits each request that is due with ``submit_time`` set to its due
time.  Only a traced run (``--trace 1``) wraps the engine's prefill and
decode calls, to count and time them, and records a profile of the last
``TRACE_SECONDS`` of the window.
"""

from __future__ import annotations

import dataclasses
import gc
import contextlib
import importlib
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import families, traffic

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CLOCK = time.monotonic

TRACE_SECONDS = 8.0     # the traced part of the window, at its end
DRAIN_SECONDS = 60.0    # open loop: how long the window's requests may take
SAMPLE_TOKENS = 256     # served tokens the check compares, at least
SAMPLE_MIN = 3          # requests the check compares, at least (if done)
SAMPLE_MAX = 24         # requests the check compares, at most
# overlay counters that must read 0 after the window: any of them means a
# call was served by something other than the assembled accelerator
ZERO_COUNTERS = ("download_failures", "dispatch_failures",
                 "dispatch_fallbacks", "breaker_opens", "resident_losses",
                 "timed_out_downloads")


LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad cell)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    benchmark: dict

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def metrics(self, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those that list it, and those that list no cells."""
        return [m for m in self.benchmark[kind]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: Path = REPO, data: Path = BENCH) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its configuration
    and mix from ``data/configs`` and ``data/traffic``."""
    bm_path = root / "BENCHMARK.json"
    if not bm_path.is_file():
        raise BenchError(f"no BENCHMARK.json in {root}")
    bm = json.loads(bm_path.read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    conf = json.loads((data / "configs" / f"{wl['config']}.json").read_text())
    return Cell(name, wl, conf, traffic.load_mix(wl["traffic"], data), bm)


def metric_reader(name: str):
    """``read`` of ``bench/metrics/<name before the first dot>.py``."""
    base = name.split(".")[0]
    return importlib.import_module(f"bench.metrics.{base}").read


# ---------------------------------------------------------------------------
# what the window served, as the host sees it
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    prefill: list[tuple[int, int, bool]]   # (offset, real tokens, is last)
    decode_ctx: list[int]                  # positions each decoded row saw


class Observer:
    """After every tick: which tokens each request got, and when."""

    def __init__(self, engine):
        self.engine = engine
        self.reqs: dict[int, object] = {}      # rid -> Request
        self.due: dict[int, float] = {}        # rid -> due time (clock)
        self.times: dict[int, list[float]] = {}
        self.slot_time: dict[int, float] = {}  # rid -> first tick in a slot
        self.ticks: list[Tick] = []
        self.prefill_log: list[tuple[int, int, bool]] = []
        self.decode_us: list[float] = []
        self.window_ticks = 0

    def offer(self, req, due: float) -> None:
        self.reqs[req.rid] = req
        self.due[req.rid] = due
        self.times[req.rid] = []

    def tick(self, t0: float, t1: float, finished) -> None:
        resident = [r for r in self.engine.slot_req if r is not None]
        ctx = []
        for r in list(resident) + list(finished):
            seen = self.times.get(r.rid)
            if seen is None:
                continue
            if r.rid not in self.slot_time:
                self.slot_time[r.rid] = t1
            for k in range(len(seen), len(r.out)):
                seen.append(t1)
                if k >= 1:
                    # decoded from position len(prompt)+k-1, attending to
                    # that many positions plus its own
                    ctx.append(len(r.prompt) + k)
        self.ticks.append(Tick(t0, t1, self.prefill_log, ctx))
        self.prefill_log = []


def _wrap_engine(engine, obs: Observer, annotate):
    """Traced runs only: count and time the engine's own prefill-chunk and
    decode calls, and mark them in the profile."""
    decode, prefill = engine._decode, engine._prefill_chunk

    def timed_decode(*args):
        t = time.perf_counter()
        with annotate("bench.decode"):
            out = decode(*args)
        obs.decode_us.append((time.perf_counter() - t) * 1e6)
        return out

    def counted_prefill(params, toks, c1, last):
        st = next(st for st in engine._prefilling.values() if st["c1"] is c1)
        off, left = st["off"], len(st["req"].prompt) - st["off"]
        n = min(toks.shape[1], left)
        obs.prefill_log.append((off, n, n == left))
        with annotate("bench.prefill_chunk"):
            return prefill(params, toks, c1, last)

    engine._decode, engine._prefill_chunk = timed_decode, counted_prefill


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunArgs:
    workload: str
    seed: int
    seconds: float
    trace: bool


def _device_check(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_chip and dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips; JAX found {len(devs)}")
    return dev, devs


def _warm_prompt_lens(lo: int, hi: int, chunk: int) -> list[int]:
    """One prompt per prefill bucket that prompts of ``lo..hi`` tokens use:
    a bucket ``b <= chunk`` is a prompt of ``b`` tokens (one chunk)."""
    sizes = set()
    for n in range(lo, hi + 1):
        for rem in range(n, 0, -chunk):
            sizes.add(chunk if rem >= chunk else 1 << (rem - 1).bit_length())
    return sorted(sizes)


def _sample(done: list, seed: int) -> list:
    """Finished requests for the check, drawn from the seed, with the one
    that served the most tokens always in."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.out), -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(rest))
    picked, total = [longest], len(longest.out)
    for i in order:
        if len(picked) >= SAMPLE_MAX or (
                total >= SAMPLE_TOKENS and len(picked) >= SAMPLE_MIN):
            break
        picked.append(rest[i])
        total += len(rest[i].out)
    return picked


class Bench:
    """The process-wide part of set-up: the device, the compile cache, the
    configuration's family and the program's modules, and a count of
    programs compiled in the window."""

    def __init__(self, cell: Cell, *, require_chip: bool = True):
        import jax

        self.jax, self.cell = jax, cell
        self.dev, self.devs = _device_check(jax, cell.chips, require_chip)
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        # every program to disk, the small eager ones too, so that a second
        # run in this checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self.compiled = self.cache_hits = 0
        self.counting = False

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if self.counting and event == LOWERING_EVENT:
                self.compiled += 1

        def on_event(event: str, **_kw) -> None:
            if self.counting and event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        self.family = families.of(cell.config)
        self.dims = self.family.Dims.from_config(cell.config)
        self.cfg = self.family.program_config(cell.config, self.dims)
        eng = cell.config["engine"]
        self.batch, self.chunk = int(eng["batch"]), int(eng["chunk"])
        self.max_len = int(eng["max_len"])

    def serve(self, seed: int):
        """Weights from the seed, the overlay, the engine, all warm."""
        jax = self.jax
        from repro.core import Overlay
        from repro.models import params as pm
        from repro.models.transformer import model_spec
        from repro.serving import EventLoopEngine, Request

        t = CLOCK()
        params = self.family.program_params(self.dims,
                                            families.root_key(seed))
        want = pm.abstract(model_spec(self.cfg))
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or not all(
                a.shape == b.shape and a.dtype == b.dtype
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise BenchError("the benchmark's weights do not fit the "
                             "program's parameter layout")
        jax.block_until_ready(params)
        t_weights = CLOCK()
        ov_conf = dict(self.cell.config["overlay"])
        tile_budget = ov_conf.pop("tile_budget", None)
        ov = Overlay(**ov_conf)
        eng = EventLoopEngine(params, self.cfg, batch=self.batch,
                              max_len=self.max_len, chunk=self.chunk,
                              overlay=ov, tile_budget=tile_budget)
        p = self.cell.mix["prompt"]
        warm_lens = _warm_prompt_lens(int(p["min"]), int(p["max"]),
                                      self.chunk)
        eng.warmup(tuple(warm_lens))
        # one short pass of every prefill bucket and a few decode ticks, so
        # that the eager programs around the engine's calls exist before
        # the window
        wrng = np.random.default_rng(seed + 1)
        for i, n in enumerate(warm_lens):
            eng.submit(Request(rid=-1 - i, max_new_tokens=2,
                               prompt=wrng.integers(0, self.dims.vocab,
                                                    n).tolist()))
        eng.run_until_drained()
        log(f"set-up: weights {t_weights - t:.3f} s, overlay warm-up and "
            f"warm pass {CLOCK() - t_weights:.3f} s, {ov.stats.downloads} "
            f"accelerators, {ov.stats.reclaims} reclaims")
        return eng

    def window(self, eng, tr: "traffic.Traffic", seconds: float, *,
               trace: bool = False, t_start: float | None = None,
               fault=None) -> "Window":
        """Serve ``tr`` for ``seconds``; then, in an open loop, let the
        window's requests finish (or fail) within ``DRAIN_SECONDS``."""
        jax = self.jax
        from repro.serving import Request

        ov, batch = eng.overlay, self.batch
        obs = Observer(eng)
        annotate = jax.profiler.TraceAnnotation
        if trace:
            _wrap_engine(eng, obs, annotate)
        if fault is not None:
            fault(eng)
        before = dict(ov.failure_ledger())
        before_stats = (ov.stats.fallback_calls, ov.stats.reclaims)
        trace_dir = None

        def span(name):
            return annotate(name) if trace else contextlib.nullcontext()

        pending, next_i, pool_i = tr.requests, 0, 0
        self.compiled, self.cache_hits, self.counting = 0, 0, True
        t0 = CLOCK()
        end, t_last = t0 + seconds, t0
        while True:
            now = CLOCK()
            if tr.closed:
                while len(eng.queue) < batch:
                    o = tr.requests[pool_i % len(tr.requests)]
                    req = Request(rid=pool_i, prompt=o.prompt,
                                  max_new_tokens=o.max_new_tokens)
                    pool_i += 1
                    obs.offer(req, now)
                    eng.submit(req)
            else:
                while next_i < len(pending) and \
                        t0 + pending[next_i].due <= now:
                    o = pending[next_i]
                    next_i += 1
                    req = Request(rid=o.rid, prompt=o.prompt,
                                  max_new_tokens=o.max_new_tokens)
                    req.submit_time = t0 + o.due
                    obs.offer(req, t0 + o.due)
                    eng.submit(req)
            if now >= end:
                break
            if trace and trace_dir is None and \
                    now >= end - min(TRACE_SECONDS, seconds):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
            if not eng.queue and all(r is None for r in eng.slot_req):
                nxt = t0 + pending[next_i].due if next_i < len(pending) \
                    else end
                with span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, end) - CLOCK()))
                continue
            with span("bench.tick"):
                finished = eng.step()
            t_last = CLOCK()
            obs.tick(now, t_last, finished)
        self.counting = False
        obs.window_ticks = len(obs.ticks)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        if not tr.closed:
            deadline = CLOCK() + DRAIN_SECONDS
            while CLOCK() < deadline and (
                    eng.queue or any(r is not None for r in eng.slot_req)):
                t_a = CLOCK()
                finished = eng.step()
                obs.tick(t_a, CLOCK(), finished)
        after = ov.failure_ledger()
        counters = {k: after[k] - before[k] for k in ZERO_COUNTERS}
        counters["fallback_calls"] = ov.stats.fallback_calls - before_stats[0]
        reclaims = ov.stats.reclaims - before_stats[1]
        log(f"window: {seconds} s, {obs.window_ticks} ticks, "
            f"{self.compiled} programs compiled in the window "
            f"({self.cache_hits} of them from the persistent cache), "
            f"{reclaims} reclaims")
        shed = {r.rid for r in eng.shed}
        return Window(obs=obs, t0=t0, t_last=t_last, closed=tr.closed,
                      counters=counters, shed=shed, trace_dir=trace_dir,
                      setup_s=(t0 - t_start) if t_start is not None else None,
                      compiled=self.compiled, cache_hits=self.cache_hits,
                      reclaims=reclaims)

    def free(self, eng) -> None:
        eng.overlay.close()
        eng.params = eng.caches = None
        gc.collect()

    def peak_bytes(self) -> int:
        return int((self.dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


@dataclasses.dataclass
class Window:
    obs: Observer
    t0: float
    t_last: float
    closed: bool
    counters: dict
    shed: set
    trace_dir: Path | None
    setup_s: float | None
    compiled: int = 0          # programs lowered in the window
    cache_hits: int = 0        # of those, loaded from the persistent cache
    reclaims: int = 0          # overlay reclaims in the window

    def requests(self) -> list:
        return list(self.obs.reqs.values())

    def done(self) -> list:
        return [r for r in self.requests() if r.done]

    def attempted_failed(self) -> tuple[int, int]:
        reqs = self.requests()
        if self.closed:
            attempted = sum(1 for r in reqs if r.rid in self.obs.slot_time)
            return attempted, len(self.shed)
        failed = sum(1 for r in reqs if r.rid in self.shed or not r.done)
        return len(reqs), failed


def check(bench: Bench, win: Window, seed: int, *, control: bool = False):
    """Compare a sample of what the window served with the reference.
    Returns (checks, correct, gaps)."""
    sample = [(list(r.prompt), list(r.out))
              for r in _sample(win.done(), seed)]
    t = CLOCK()
    gaps = bench.family.served_gaps(bench.dims, seed, sample,
                                    control=control) \
        if sample else {"program": np.array([np.inf])}
    widest = float(np.max(gaps["program"]))
    log(f"check: reference over {len(sample)} requests, "
        f"{gaps['program'].size if sample else 0} served tokens, "
        f"{CLOCK() - t:.3f} s")
    limit = float(bench.cell.config["check"]["logit_gap_limit"])
    checks = {"logit_gap": {"value": widest, "limit": limit}}
    for k, v in win.counters.items():
        checks[k] = {"value": int(v), "limit": 0}
    correct = bool(sample) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    return checks, correct, gaps


def run(args: RunArgs, *, t_start: float, require_chip: bool = True,
        root: Path = REPO, data: Path = BENCH, fault=None) -> dict:
    """One run of a cell; returns the result object (the last line).
    ``fault``, where given, is called with the engine before the window:
    the harness's own tests use it to break the timed path."""
    cell = load_cell(args.workload, root, data)
    bench = Bench(cell, require_chip=require_chip)
    eng = bench.serve(args.seed)
    tr = traffic.generate(cell.mix, seed=args.seed, seconds=args.seconds,
                          vocab=bench.dims.vocab, batch=bench.batch)
    win = bench.window(eng, tr, args.seconds, trace=args.trace,
                       t_start=t_start, fault=fault)
    peak = bench.peak_bytes()
    attempted, failed = win.attempted_failed()
    e2e = _end_to_end(win.obs, win.t0, win.t_last, win.setup_s)
    bench.free(eng)
    del eng
    checks, correct, _ = check(bench, win, args.seed)

    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed)}
    device_extra, breakdown = {}, None
    if args.trace:
        metrics, device_extra, breakdown = _per_layer(bench, win.obs,
                                                      win.trace_dir)
        if win.trace_dir is not None:
            shutil.rmtree(win.trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")
                   if e2e.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = {"platform": bench.dev.platform,
                        "kind": bench.dev.device_kind, "count": len(bench.devs),
                        "memory_peak_bytes": peak, **device_extra}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']} limit {v['limit']}")
    return result


def _end_to_end(obs: Observer, t0: float, t_last: float,
                setup_s: float) -> dict:
    window = max(t_last - t0, 1e-9)
    tokens = sum(1 for ts in obs.times.values() for t in ts if t <= t_last)
    ttft, itl = [], []
    for rid, ts in obs.times.items():
        due = obs.due[rid]
        ttft.append((ts[0] - due) if ts else math.inf)
        itl.extend(b - a for a, b in zip(ts, ts[1:]))
    ttft.sort()
    itl.sort()
    return {
        "setup_s": setup_s,
        "out_tokens_per_s": tokens / window,
        "ttft_p90_ms": traffic.percentile(ttft, 0.90) * 1e3,
        "itl_p95_ms": traffic.percentile(itl, 0.95) * 1e3 if itl else None,
    }


def _per_layer(bench: Bench, obs: Observer, trace_dir):
    from bench import trace_reduce

    cell = bench.cell
    peaks = trace_reduce.peaks_for(bench.dev.device_kind)
    tr, traced_ticks = None, []
    if trace_dir is not None:
        tr = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        k = len(tr.tick_busy_s)
        traced_ticks = obs.ticks[obs.window_ticks - k:obs.window_ticks]
    ctx = MetricContext(family=bench.family, dims=bench.dims,
                        config=cell.config, peaks=peaks, obs=obs, trace=tr,
                        traced_ticks=traced_ticks)
    metrics = {}
    for m in cell.metrics("per_layer"):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extra, breakdown = {}, None
    if tr is not None:
        extra = {"busy_s": tr.busy_s, "window_s": tr.window_s}
        breakdown = {"device_ops": tr.top_ops, "idle_gaps": tr.idle_gaps}
    return metrics, extra, breakdown


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader may read."""

    family: object             # the configuration's module of bench.families
    dims: object               # family.Dims of the configuration
    config: dict
    peaks: dict
    obs: Observer
    trace: object              # trace_reduce.Reduced, or None
    traced_ticks: list         # the Ticks of the traced window, in order
