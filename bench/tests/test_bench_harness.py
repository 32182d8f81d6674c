"""CPU tests of the benchmark harness: the arithmetic of its metrics, the
trace reduction, discovery of cells and architectures by name, the
dense architecture's refusal of what it does not serve, and the
harness's refusal to run without a TPU.  Nothing here touches a TPU or
describes one."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import reference, run, trace_reduce
from bench.traffic import (Stream, bucket_len, buckets, load_mix,
                           max_len_for, seed_sequence)
from bench.weights import load_arch, load_config

ROOT = Path(__file__).resolve().parents[2]
DEEPSEEK = json.loads((ROOT / "bench/configs/deepseek-7b.json").read_text())


# -- end-to-end arithmetic ---------------------------------------------------

def _window(calls):
    return run.Window(calls=calls, stats={}, compiles=0, vocab=100,
                      model=None, peaks={}, n_devices=1)


def _call(start, end, outs):
    jobs = [run.Job(uid=i, prompt=np.zeros(3, np.int32), max_new=len(o))
            for i, o in enumerate(outs)]
    return run.Call(start, end, jobs, [list(o) for o in outs])


def test_tokens_per_s_counts_all_tokens_over_all_calls():
    w = _window([_call(10.0, 11.0, [[1, 2], [3]]),
                 _call(11.0, 12.5, [[4, 5, 6, 7]])])
    assert w.tokens == 7
    assert run.tokens_per_s(w) == pytest.approx(7 / 2.5)


def test_malformed_stream_is_failed_and_not_served():
    w = _window([_call(0.0, 1.0, [[1, 2], [3]])])
    w.calls[0].outs[1] = [3, 100]            # out of vocab, and too long
    assert w.attempted == 2 and len(w.served()) == 1 and w.tokens == 2


def test_request_p95_is_over_requests_each_at_its_call_latency():
    # 19 requests of one call at 100 ms, one of another at 1000 ms
    calls = [_call(0.0, 0.1, [[1]] * 19), _call(0.1, 1.1, [[1]])]
    lat = sorted([100.0] * 19 + [1000.0])
    want = float(np.quantile(lat, 0.95))     # the inclusive definition
    got = run.request_ms_p95(_window(calls))
    assert got == pytest.approx(want)
    assert got == pytest.approx(100.0 + 0.05 * 900.0)


def test_check_compares_every_number_its_limits_name():
    """Drafts rejected come from the window's counters; a number named in
    the limits that the run gives none of (no request served, so no
    sample for the reference) fails."""
    w = _window([_call(0.0, 1.0, [[1, 2]])])
    w.calls[0].outs[0] = [1]                 # malformed: no sample
    w.stats = {"drafted_tokens": 400, "draft_hits": 330}
    mix = load_mix(ROOT, "chat")
    checks, ctrl, readings = run.check(
        {"draft_miss_share": 40.0, "max_logit_gap": 1.5}, None, mix, 792, 0,
        7, w)
    assert checks["failed_requests"] == (1.0, 0.0)
    assert checks["draft_miss_share"] == (pytest.approx(17.5), 40.0)
    assert checks["max_logit_gap"] == (float("inf"), 1.5)
    assert not run.passes(checks) and ctrl == {}
    assert run.passes({"draft_miss_share": (17.5, 40.0)})


def test_reference_numbers_from_gaps():
    got = reference.numbers([np.array([0.0, 0.5, 0.0]),
                             np.array([0.0, 0.0, 0.0, 2.5])])
    assert got == {"max_logit_gap": 2.5,
                   "mean_logit_gap": pytest.approx(3.0 / 7),
                   "argmax_miss_share": pytest.approx(100 * 2 / 7)}


# -- model FLOPs ---------------------------------------------------------------

def test_request_flops_against_a_hand_count():
    dense = load_arch(ROOT, DEEPSEEK)
    m = dense.model_of(dict(
        DEEPSEEK, hidden_size=64, intermediate_size=172,
        num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=2,
        vocab_size=256))
    # block matmuls: q, o 64x64 each; k, v 64x64 each; wi, wg, wo 64x172
    assert m.block_matmul_params() == 4 * 64 * 64 + 3 * 64 * 172 == 49408
    # prompt 5, served 3: positions 0..6 run (the last token is not fed)
    linear = 2 * 49408 * 2 * 7
    attn = 2 * 2 * 4 * 16 * 2 * sum(t + 1 for t in range(7))
    head = 2 * 64 * 256 * 3
    assert dense.request_flops(m, 5, 3) == linear + attn + head == 1496064


# -- what an architecture serves -------------------------------------------

@pytest.mark.parametrize("key,value", [
    (None, None), ("num_experts", 64), ("sliding_window", 1024),
    ("rms_norm_eps", 1e-5), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True)])
def test_dense_refuses_what_the_program_would_not_serve_as_stated(key,
                                                                   value):
    """deepseek-7b's file loads as it stands; a key the dense file does
    not serve, or a value of one it reads that the program would not
    apply, is refused by name before anything is built."""
    dense = load_arch(ROOT, DEEPSEEK)
    if key is None:
        m = dense.model_of(DEEPSEEK)
        assert (m.head_dim, m.num_hidden_layers) == (128, 7)
        return
    with pytest.raises(ValueError, match=key):
        dense.model_of(dict(DEEPSEEK, **{key: value}))


# -- traffic ---------------------------------------------------------------------

def test_every_seed_serves_the_same_waves_in_another_order():
    mix = load_mix(ROOT, "chat")
    first = [sorted((len(j.prompt), j.max_new) for j in Stream(
        mix, 1000, seed_sequence(s)).wave()) for s in (3, 2 ** 31 + 11)]
    waves = [sorted(map(tuple, w.tolist())) for w in mix.waves()]
    assert all(f in waves for f in first)
    for s in (5, -7, 2 ** 40 + 1):
        st = Stream(mix, 1000, seed_sequence(s))
        got = sorted(sorted((len(j.prompt), j.max_new) for j in st.wave())
                     for _ in waves)
        assert got == sorted(waves)            # one pass = every wave once


@pytest.mark.parametrize("name", ["chat", "longdoc"])
def test_every_wave_holds_one_pair_of_each_output_stratum(name):
    mix = load_mix(ROOT, name)
    pairs = mix.lengths()
    waves = mix.waves()
    assert sorted(map(tuple, np.concatenate(waves).tolist())) == \
        sorted(map(tuple, pairs.tolist()))
    n = len(waves)
    cuts = np.sort(pairs[:, 1])[::-1]
    for s in range(mix.wave):
        lo, hi = cuts[(s + 1) * n - 1], cuts[s * n]
        assert all(sum(lo <= o <= hi for o in w[:, 1]) >= 1 for w in waves)
    # about the same answer tokens in every wave, and the same longest
    tokens = [int(w[:, 1].sum()) for w in waves]
    assert max(tokens) - min(tokens) <= 0.1 * np.mean(tokens)
    if name == "chat":
        assert {int(w[:, 1].max()) for w in waves} == {256}


def test_same_seed_same_tokens():
    mix = load_mix(ROOT, "chat")
    a = Stream(mix, 1000, seed_sequence(2 ** 33 + 5)).wave()
    b = Stream(mix, 1000, seed_sequence(2 ** 33 + 5)).wave()
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))


@pytest.mark.parametrize("name,lo,hi", [("chat", 1, 512),
                                        ("longdoc", 1025, 2048)])
def test_mix_lengths_stay_in_their_stated_range(name, lo, hi):
    mix = load_mix(ROOT, name)
    p = mix.lengths()[:, 0]
    assert p.min() >= lo and p.max() <= hi
    ml = max_len_for(mix, 4)
    assert all(bucket_len(int(x), ml) >= x for x in p)
    if name == "longdoc":
        assert buckets(mix, ml) == [2048]


# -- trace reduction -------------------------------------------------------------

@dataclasses.dataclass
class _Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


@dataclasses.dataclass
class _Profile:
    planes: list


def _device_trace():
    """Two generate calls on one device: [100, 200) and [250, 400) ns,
    shaped as a TPU trace is: ops named by their HLO text, a while loop
    holding its body's ops.  Ops (ns): a while loop 110-170 holding
    fusion.1 at 110-130 and the kernel at 160-170, fusion.2 at 120-150
    overlapping fusion.1 inside the loop, a prefill convolution 260-300,
    an op at 500-600 outside the window, and an async copy (left out)."""
    mods = _Line("XLA Modules", [_Ev("jit__unknown(123)", 105, 70),
                                 _Ev("jit__edge_prefill_impl(9)", 255, 50),
                                 _Ev("jit_concatenate(7)", 490, 120)])
    ops = _Line("XLA Ops", [
        _Ev("%while.4 = (s32[], bf16[4,4,4096]) while(%tuple)", 110, 60),
        _Ev("%fusion.1 = bf16[4] fusion(%p)", 110, 20),
        _Ev("%fusion.2 = bf16[4] fusion(%q)", 120, 30),
        _Ev("%paged_flash_mq.8 = f32[4,32,4,128] custom-call(%a)", 160, 10),
        _Ev("%convolution.3 = bf16[8] convolution(%x, %y)", 260, 40),
        _Ev("%fusion.9 = f32[] fusion(%z)", 500, 100)])
    copies = _Line("Async XLA Ops", [_Ev("%copy-start.2 = s8[9]", 100, 300)])
    host = _Line("python", [
        _Ev(trace_reduce.WINDOW_SPAN, 100, 100),
        _Ev(trace_reduce.GAP_SPAN, 200, 50),
        _Ev(trace_reduce.WINDOW_SPAN, 250, 150)])
    return _Profile([_Plane("/device:TPU:0", [mods, ops, copies]),
                     _Plane("/host:CPU", [host])])


def test_reduce_busy_union_and_idle_share():
    r = trace_reduce.reduce_profile(_device_trace())
    assert r.window == (100, 400)
    assert r.devices == ["/device:TPU:0"]
    # union: 110-170 (60) + 260-300 (40) = 100 ns
    assert r.busy_ns == pytest.approx(100)
    assert r.window_s == pytest.approx(300e-9)
    assert 1 - r.busy_s / r.window_s == pytest.approx(2 / 3)


def test_reduce_attributes_ops_to_modules_and_kernels():
    r = trace_reduce.reduce_profile(_device_trace())
    # modules: the program events clipped to the window
    assert r.module_ns == {"jit__unknown(123)": pytest.approx(70),
                           "jit__edge_prefill_impl(9)": pytest.approx(50)}
    prefill = trace_reduce.module_is("_edge_prefill_impl")
    assert r.module_time(prefill) == pytest.approx(50e-9)
    # self time: the loop's 60 ns less fusion.1 (20) and the kernel (10);
    # fusion.2 overlaps fusion.1 without nesting in it
    selfs = {o.name: o.self_ns for o in r.ops}
    assert selfs["while.4"] == pytest.approx(30)
    assert selfs["fusion.1"] == pytest.approx(20)
    kernel = trace_reduce.op_is("paged_flash")
    assert r.op_time(kernel) == pytest.approx(10e-9)
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["jit__edge_prefill_impl(9)/convolution.3",
                                   pytest.approx(40e-9)]
    assert all(len(k) < 80 for k, _ in bd["device_ops"])
    # idle stretches: 100-110, 170-260 (the gap span holds 215), 300-400
    assert [g[0] for g in bd["idle_gaps"]] == [
        trace_reduce.WINDOW_SPAN, trace_reduce.GAP_SPAN,
        trace_reduce.WINDOW_SPAN]
    assert [g[1] for g in bd["idle_gaps"]] == [
        pytest.approx(100e-9), pytest.approx(90e-9), pytest.approx(10e-9)]


def test_readers_of_a_chip_shaped_trace():
    r = trace_reduce.reduce_profile(_device_trace())
    w = run.Window(calls=[_call(0.0, 1.0, [[1]])], stats={"spec_rounds": 2},
                   compiles=0, vocab=10, model=None, peaks={}, n_devices=1,
                   trace=r)
    got = {name: run.load_reader(ROOT, name)(w) for name in (
        "phase.prefill_share", "phase.round_ms", "kernel.paged_attn_share",
        "device.idle_share")}
    assert got["phase.prefill_share"] == pytest.approx(100 * 50 / 300)
    # only jit__ phases that are no prefill count: 70 ns over 2 rounds
    assert got["phase.round_ms"] == pytest.approx(1e3 * 70e-9 / 2)
    assert got["kernel.paged_attn_share"] == pytest.approx(100 * 10 / 100)
    assert got["device.idle_share"] == pytest.approx(100 * 2 / 3)


def test_reduce_needs_the_window_spans():
    p = _device_trace()
    p.planes[1].lines[0].events = []
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(p)


def test_reduce_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation(trace_reduce.GAP_SPAN):
            pass
    jax.profiler.stop_trace()
    r = trace_reduce.reduce_dir(str(tmp_path))
    assert 0 < r.busy_s <= r.window_s
    assert any("jit" in m for m in r.module_ns)
    assert sum(o.self_ns for o in r.ops) >= r.busy_ns * 0.999
    assert len(r.breakdown()["device_ops"]) <= 10


# -- metric readers and discovery by name --------------------------------------

def test_every_per_layer_metric_has_a_reader_and_readers_stay_silent():
    bench = run.load_benchmark(ROOT)
    empty = run.Window(calls=[_call(0.0, 1.0, [[]])], stats={
        "decode_steps": 0, "decode_tokens": 0, "drafted_tokens": 0,
        "draft_hits": 0, "spec_rounds": 0, "transmitted_bytes": 0,
        "channel_latency_s": 0.0}, compiles=0, vocab=10, model=None,
        peaks={}, n_devices=1)
    for m in bench["per_layer"]:
        read = run.load_reader(ROOT, m["name"])
        v = read(empty)
        # nothing to read: no value, except a count that is really 0
        assert v is None or m["unit"] == "count"


def test_cells_configs_and_mixes_are_found_by_name():
    bench = run.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        conf = load_config(ROOT, run.find(bench["configs"], cell["config"],
                                          "configuration"))
        load_arch(ROOT, conf).model_of(conf)
        load_mix(ROOT, cell["traffic"])
        assert run.load_limits(ROOT, cell["name"])["max_logit_gap"] > 0
        assert run.cell_metrics(bench, cell["name"], "end_to_end")
        assert run.cell_metrics(bench, cell["name"], "per_layer")


def test_unknown_names_and_missing_files_fail(tmp_path):
    bench = run.load_benchmark(ROOT)
    with pytest.raises(KeyError):
        run.find(bench["workloads"], "no-such.cell", "workload")
    with pytest.raises(FileNotFoundError):
        load_mix(ROOT, "no-such-mix")
    with pytest.raises(FileNotFoundError):
        run.load_reader(ROOT, "no.such_metric")
    with pytest.raises(FileNotFoundError):
        load_config(ROOT, {"file": "bench/configs/no-such.json"})
    with pytest.raises(FileNotFoundError, match="no-such-arch.py"):
        load_arch(ROOT, {"arch": "no-such-arch"})
    with pytest.raises(FileNotFoundError):
        run.load_limits(ROOT, "no-such.cell")
    with pytest.raises(FileNotFoundError):
        run.load_benchmark(tmp_path)
    assert run.load_peaks(ROOT, "TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks(ROOT, "TPU v9 imaginary")


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A throwaway architecture, configuration, mix and per-layer metric
    are added as new files plus new BENCHMARK.json entries; no existing
    file of the benchmark is edited, and the harness finds all four by
    name."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/deepseek-7b.json").read_text())
    conf.update(name="throwaway", arch="wide", num_hidden_layers=2)
    (tmp_path / "bench/configs/throwaway.json").write_text(json.dumps(conf))
    (tmp_path / "bench/archs/wide.py").write_text(
        (ROOT / "bench/archs/dense.py").read_text()
        + "\n\nKIND = 'wide'\n")
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    mix.update(wave=4, pool=8)
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/throwaway.burst.json").write_text(
        json.dumps({"max_logit_gap": 1.0}))
    (tmp_path / "bench/metrics/calls.count.py").write_text(
        "def read(w):\n    return float(len(w.calls))\n")
    bench["configs"].append({"name": "throwaway", "source": "x",
                             "file": "bench/configs/throwaway.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "throwaway.burst",
                               "config": "throwaway", "traffic": "burst",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "calls.count", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler", "moves": "tokens_per_s",
                               "workloads": ["throwaway.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    b = run.load_benchmark(tmp_path)
    cell = run.find(b["workloads"], "throwaway.burst", "workload")
    conf = load_config(tmp_path, run.find(b["configs"], cell["config"],
                                          "c"))
    arch = load_arch(tmp_path, conf)
    assert arch.KIND == "wide"
    m = arch.model_of(conf)
    assert m.num_hidden_layers == 2 and isinstance(m, arch.Model)
    assert load_mix(tmp_path, "burst").wave == 4
    names = [x["name"] for x in run.cell_metrics(b, "throwaway.burst",
                                                 "per_layer")]
    assert "calls.count" in names
    assert "calls.count" not in [x["name"] for x in run.cell_metrics(
        b, "deepseek-7b.chat", "per_layer")]
    assert run.load_limits(tmp_path, "throwaway.burst")["max_logit_gap"] == 1
    read = run.load_reader(tmp_path, "calls.count")
    assert read(_window([_call(0.0, 1.0, [[1]])] * 3)) == 3.0
    assert all(p.read_bytes() == data for p, data in before.items())


# -- the command ---------------------------------------------------------------

def _bench_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "deepseek-7b.chat", "--seed", str(2 ** 31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=_bench_env(),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = _bench_env()
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deepseek-7b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
