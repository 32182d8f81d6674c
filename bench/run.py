#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration file, the architecture file the configuration's
``arch`` names (``bench/archs/<arch>.py``: model, weights, engine
config, FLOP count and the reference's block; ``bench/weights.py``),
``bench/traffic/<mix>.json``, and with ``--trace 1`` one reader per
per-layer metric, ``bench/metrics/<name>.py``.

One process that starts no children.  It needs the chips the cell asks
for: without a TPU it exits non-zero and prints no result.  It builds
the model with weights made on the device from ``--seed``, builds the
collaborative engine, warms up every shape the cell's traffic can make
(each prefill bucket at each group size, then one wave), and then drives
a closed loop for ``--seconds``: back-to-back ``generate_requests``
calls, each carrying one wave of the mix, up to the call that ends after
``--seconds``.  Every wave of a mix is about the same work
(``bench/traffic.py``), so every seed serves the same work.  Afterwards
it frees the engine, checks the window's drafts and a seeded sample of
the served requests against the plain reference (``bench/reference.py``),
and prints one JSON line last.

End-to-end metrics (``--trace 0``): ``tokens_per_s``, ``request_ms_p95``
and ``setup_s``, all by the host clock.  ``--trace 1`` runs the same
window under the profiler, with host spans around each call and each gap
between calls, and prints the per-layer metrics instead.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import dataclasses                                             # noqa: E402
import gc                                                      # noqa: E402
import json                                                    # noqa: E402
import os                                                      # noqa: E402
import shutil                                                  # noqa: E402
import statistics                                              # noqa: E402
import sys                                                     # noqa: E402
import tempfile                                                # noqa: E402
from contextlib import nullcontext                             # noqa: E402
from pathlib import Path                                       # noqa: E402
from typing import (Any, Callable, Dict, List, Optional,       # noqa: E402
                    Sequence)

import numpy as np                                             # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, trace_reduce                      # noqa: E402
from bench.traffic import (Job, Stream, bucket_len, buckets,   # noqa: E402
                           load_mix, max_len_for, seed_sequence)
from bench.weights import load_arch, load_config, module_at    # noqa: E402

CACHE_DIR = ".jax_cache"         # inside the checkout, at a fixed path


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what the cell is made of ------------------------------------------------

def load_benchmark(root: Path) -> Dict[str, Any]:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_peaks(root: Path, kind: str) -> Dict[str, Any]:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def load_limits(root: Path, workload: str) -> Dict[str, float]:
    """The limits of the numbers a cell's check compares, set from
    readings of the program, of its controls and of planted faults
    (``PERF.md``); the check compares every number the file names, and
    ``failed_requests`` at 0."""
    path = Path(root) / "bench" / "limits" / f"{workload}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no check limits for {workload!r} at {path}")
    return json.loads(path.read_text())


def load_reader(root: Path, name: str) -> Callable:
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    return module_at(path).read


def cell_metrics(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") that this
    cell reports: those with no ``workloads`` key, and those naming it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- the run's record --------------------------------------------------------

@dataclasses.dataclass
class Call:
    start: float
    end: float
    jobs: List[Job]
    outs: List[List[int]]


@dataclasses.dataclass
class Window:
    """What one measured window gives the metric readers."""
    calls: List[Call]
    stats: Dict[str, float]            # counter deltas over the window
    compiles: int                      # backend compiles inside it
    vocab: int
    model: Any
    peaks: Dict[str, Any]
    n_devices: int
    trace: Optional[trace_reduce.Reduced] = None
    arch: Any = None                   # the model's bench/archs/<arch>.py

    @property
    def wall_s(self) -> float:
        return self.calls[-1].end - self.calls[0].start

    def served(self) -> List[tuple]:
        """(job, tokens) of every request whose stream is well formed."""
        return [(j, o) for c in self.calls for j, o in zip(c.jobs, c.outs)
                if len(o) == j.max_new and all(0 <= t < self.vocab
                                               for t in o)]

    @property
    def attempted(self) -> int:
        return sum(len(c.jobs) for c in self.calls)

    @property
    def tokens(self) -> int:
        return sum(len(o) for _, o in self.served())

    def delta(self, name: str) -> float:
        return self.stats[name]

    def flops(self) -> float:
        return sum(self.arch.request_flops(self.model, len(j.prompt),
                                           len(o))
                   for j, o in self.served())


def tokens_per_s(w: Window) -> float:
    return w.tokens / w.wall_s


def request_ms_p95(w: Window) -> float:
    """95th percentile of request latency: a request ends when the call
    that carries it returns its tokens."""
    lat = [1e3 * (c.end - c.start) for c in w.calls for _ in c.jobs]
    if len(lat) < 2:
        return lat[0]
    return statistics.quantiles(lat, n=20, method="inclusive")[18]


def counters(stats) -> Dict[str, float]:
    return {f.name: getattr(stats, f.name) for f in dataclasses.fields(stats)
            if isinstance(getattr(stats, f.name), (int, float))}


def use_cache(jax, on: bool) -> None:
    """Turn the persistent compilation cache on or off for the compiles
    that follow; programs already in memory are kept."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


class CompileClock:
    """Programs compiled or loaded from the persistent cache, and their
    seconds, from JAX's own monitoring events."""

    def __init__(self, jax):
        self.secs = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# -- the run -----------------------------------------------------------------

def warm_jobs(mix, stream: Stream, max_len: int, slots: int, page_size: int,
              spec_k: int) -> List[List[Job]]:
    """Calls that compile every program the mix's traffic can run.

    Prefill: each bucket at each group size up to the slot count (the
    scheduler groups same-bucket prompts into one call).  Decode rounds:
    the draft and verify programs take the page pool's block table
    trimmed to the next power of two of the pages its busiest slot holds
    (``kvcache._PagedPool.table_dev``), so one request of each width the
    mix can produce, the one with the fewest tokens to serve."""
    pool = mix.lengths()
    waves = []
    for b in buckets(mix, max_len):
        plen = int(max(p for p in pool[:, 0]
                       if bucket_len(int(p), max_len) == b))
        for g in range(1, min(slots, mix.wave) + 1):
            waves.append([stream.job(plen, 2) for _ in range(g)])
    per_slot = -(-max_len // page_size)
    by_width: Dict[int, tuple] = {}
    for plen, out in (tuple(int(v) for v in r) for r in pool):
        pages = -(-max(plen + out + spec_k - 1, bucket_len(plen, max_len))
                  // page_size)
        width = 1
        while width < pages:
            width *= 2
        width = min(width, per_slot)
        if width not in by_width or out < by_width[width][1]:
            by_width[width] = (plen, out)
    waves += [[stream.job(*by_width[w])] for w in sorted(by_width)]
    return waves


def build_requests(jobs: List[Job], request_cls) -> list:
    return [request_cls(uid=j.uid, prompt=j.prompt, max_new_tokens=j.max_new)
            for j in jobs]


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, need_chip: bool = True, controls: Sequence[str] = ()
        ) -> Dict[str, Any]:
    """One run of ``workload``; ``controls`` also holds the reference at
    those lower precisions (``reference.CONTROLS``), read on the same
    sample, to the same limits (``bench/control.py``)."""
    root = Path(root)
    bench = load_benchmark(root)
    cell = find(bench["workloads"], workload, "workload")
    conf = load_config(root, find(bench["configs"], cell["config"],
                                  "configuration"))
    arch = load_arch(root, conf)
    model = arch.model_of(conf)
    mix = load_mix(root, cell["traffic"])
    limits = load_limits(root, workload)
    eng_conf = conf["engine"]
    kind = "per_layer" if trace else "end_to_end"
    wanted = cell_metrics(bench, workload, kind)
    readers = {m["name"]: load_reader(root, m["name"]) for m in wanted} \
        if trace else {}

    import jax
    devs = jax.devices()
    if need_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
        if len(devs) < int(cell["chips"]):
            raise NoChip(f"cell {workload} needs {cell['chips']} chips, "
                         f"JAX found {len(devs)}")
    peaks = load_peaks(root, devs[0].device_kind) if need_chip else {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}")

    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from repro.core.costmodel import Channel
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import auto_cut
    from repro.models.transformer import LMConfig
    from repro.serve.engine import CollaborativeServingEngine, Request

    log(f"compile cache: {enable_compile_cache()}")
    # set-up writes every program it compiles, however quick, to the
    # cache, so a second run of a cell in a checkout compiles none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock(jax)
    cfg = arch.engine_config(model, LMConfig)
    slots, spec_k = int(eng_conf["slots"]), int(eng_conf["spec_k"])
    max_len = max_len_for(mix, spec_k)
    channel = Channel.from_kbps(float(mix.link["bandwidth_kbps"]),
                                rtt_ms=float(mix.link["rtt_ms"]))
    cut = auto_cut(cfg, channel, mix.median_prompt()) \
        if eng_conf["cut"] == "auto" else int(eng_conf["cut"])

    params = arch.make_params(model, seed)
    jax.block_until_ready(params)
    eng = CollaborativeServingEngine(
        params, cfg, cut_layer=cut, channel=channel, max_len=max_len,
        max_batch=slots, spec_k=spec_k, page_size=int(eng_conf["page_size"]),
        a_bits=int(eng_conf["a_bits"]), edge_int8=bool(eng_conf["edge_int8"]),
        cloud_int8=bool(eng_conf["cloud_int8"]))
    del params
    held = max(d.memory_stats()["bytes_in_use"] for d in devs) \
        if need_chip else 0
    log(f"engine: cut {cut}, max_len {max_len}, slots {slots}, k {spec_k}; "
        f"bytes_in_use {held}")

    streams = seed_sequence(seed).spawn(4)
    warm = Stream(mix, model.vocab_size, streams[2])
    t0 = time.perf_counter()
    n_warm = 0
    for jobs in warm_jobs(mix, warm, max_len, slots,
                          int(eng_conf["page_size"]), spec_k) + [warm.wave()]:
        eng.generate_requests(build_requests(jobs, Request))
        n_warm += 1
    log(f"warm-up: {n_warm} calls in {time.perf_counter() - t0:.3f} s; "
        f"compiles so far {clock.count} ({clock.secs:.3f} s, "
        f"{clock.hits} cache hits)")
    # the scheduler compiles a concatenation of each call's token blocks
    # inside the window, in a shape that depends on the call; the window
    # neither reads nor writes the cache, so every run pays for its own,
    # as a fresh server would, whatever ran before in this checkout
    use_cache(jax, False)

    stream = Stream(mix, model.vocab_size, streams[3])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = jax.profiler.TraceAnnotation if trace else (
        lambda _name: nullcontext())
    s0 = counters(eng.stats)
    c0, cs0, ch0 = clock.count, clock.secs, clock.hits
    calls: List[Call] = []
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    while True:
        with span(trace_reduce.GAP_SPAN):
            jobs = stream.wave()
            reqs = build_requests(jobs, Request)
        with span(trace_reduce.WINDOW_SPAN):
            a = time.perf_counter()
            eng.generate_requests(reqs)
            b = time.perf_counter()
        calls.append(Call(a, b, jobs, [list(r.out_tokens) for r in reqs]))
        if b - t_window >= seconds:
            break
    if trace:
        jax.profiler.stop_trace()
    use_cache(jax, True)
    compiles = clock.count - c0
    s1 = counters(eng.stats)
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devs) \
        if need_chip else 0
    device["memory_peak_bytes"] = int(peak)
    w = Window(calls=calls, stats={k: s1[k] - s0[k] for k in s1},
               compiles=compiles, vocab=model.vocab_size, model=model,
               peaks=peaks, n_devices=int(cell["chips"]), arch=arch)
    log(f"window: {len(calls)} generate calls, {w.attempted} requests, "
        f"{w.tokens} tokens served, {w.wall_s:.6f} s; {compiles} compiles "
        f"inside it ({clock.secs - cs0:.3f} s, {clock.hits - ch0} cache "
        f"hits)")

    del eng
    gc.collect()

    metrics: Dict[str, Dict[str, Any]] = {}
    units = {m["name"]: m["unit"] for m in wanted}
    if trace:
        w.trace = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
        for name, read in readers.items():
            v = read(w)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
    else:
        e2e = {"tokens_per_s": lambda: tokens_per_s(w),
               "request_ms_p95": lambda: request_ms_p95(w),
               "setup_s": lambda: setup_s}
        for name in units:
            metrics[name] = {"value": e2e[name](), "unit": units[name]}

    checks, ctrl, readings = check(limits, model, mix, max_len, cut, seed,
                                   w, controls)
    result = {"correct": passes(checks),
              "attempted": w.attempted,
              "failed": w.attempted - len(w.served()),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = w.trace.breakdown()
    if controls:
        result["controls"] = {c: {"correct": passes(ck), "check": shown(ck)}
                              for c, ck in ctrl.items()}
        result["readings"] = readings
    result["check"] = shown(checks)
    return result


def passes(checks: Dict[str, tuple]) -> bool:
    return all(v <= lim for v, lim in checks.values())


def shown(checks: Dict[str, tuple]) -> Dict[str, Dict[str, float]]:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def sample(w: Window, seed: int, tokens: int) -> List[tuple]:
    """The longest served request, then others drawn from the seed, until
    the sample holds ``tokens`` served tokens."""
    served = w.served()
    if not served:
        return []
    order = list(np.random.default_rng(seed_sequence(seed).spawn(4)[1])
                 .permutation(len(served)))
    longest = max(range(len(served)), key=lambda i: len(served[i][1]))
    order.remove(longest)
    picked, n = [], 0
    for i in [longest] + order:
        if n >= tokens:
            break
        picked.append(served[i])
        n += len(served[i][1])
    return picked


def check(limits, model, mix, max_len, cut, seed, w: Window,
          controls: Sequence[str] = ()):
    """The numbers compared, each as (value, limit), and every number
    read (``readings``, for setting limits): requests whose stream
    came back malformed (limit 0); the share of the window's drafts that
    the cloud rejected; and, over a seeded sample of served requests, the
    widest and the mean gap of a served token's logit below the
    reference's best and the share of served tokens that are not its
    first choice (``reference.numbers``).  A number the limits file names
    and the run gives none of fails.  Per control named, its numbers held
    to the same limits."""
    lims = {"failed_requests": 0.0,
            **{k: float(v) for k, v in limits.items()}}
    found = {"failed_requests": float(w.attempted - len(w.served()))}
    drafted = w.stats.get("drafted_tokens", 0)
    if drafted > 0:
        found["draft_miss_share"] = \
            100.0 * (1.0 - w.stats["draft_hits"] / drafted)
    picked = sample(w, seed, int(mix.spec["check_tokens"]))
    low: Dict[str, list] = {}
    if picked:
        params = w.arch.make_params(model, seed)
        smp = [reference.Sample(prompt=j.prompt, served=np.asarray(o),
                                bucket=bucket_len(len(j.prompt), max_len))
               for j, o in picked]
        t0 = time.perf_counter()
        gaps, low = reference.served_gaps(
            w.arch.block, model, reference.Lattice(cut=cut), params, smp,
            prompt_len=buckets(mix, max_len)[-1],
            served_len=mix.max_output(), controls=controls)
        del params
        found.update(reference.numbers(gaps))
        log(f"reference: {len(smp)} requests, {sum(map(len, gaps))} served "
            f"tokens, {time.perf_counter() - t0:.3f} s")
    for name, value in found.items():
        log(f"reading {name}: {value!r}")
    checks = {k: (found.get(k, float("inf")), lim) for k, lim in lims.items()}
    ctrl, readings = {}, {"program": found}
    for c in controls:
        got = readings[c] = reference.numbers(low[c]) if picked else {}
        ctrl[c] = {k: (got.get(k, float("inf")), lims[k]) for k in lims
                   if k in reference.NUMBERS}
        for name, (value, limit) in ctrl[c].items():
            log(f"control {c} {name}: {value!r} (limit {limit!r})")
    for name, (value, limit) in checks.items():
        log(f"check {name}: {value!r} (limit {limit!r})")
    return checks, ctrl, readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / CACHE_DIR)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
