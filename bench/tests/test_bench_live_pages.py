"""CPU test of ``kernel.paged_live_page_share``, the share of the paged
kernel's page visits that read a live key, on a traced window of the
smoke-size cell (``test_bench_check``)."""
from __future__ import annotations

import json

from bench import run
from bench.tests.test_bench_check import CELL, SEED, _tiny_root

METRIC = "kernel.paged_live_page_share"


def test_a_traced_window_reads_the_live_page_share(tmp_path):
    root = _tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run.run(root, CELL, SEED, 0.5, True, need_chip=False)
    got = res["metrics"][METRIC]
    assert got["unit"] == "%" and 0 < got["value"] < 100


def test_a_program_without_the_counters_reads_nothing():
    read = run.load_reader(run.ROOT, METRIC)
    w = run.Window(calls=[], stats={"spec_rounds": 3}, compiles=0, vocab=10,
                   model=None, peaks={}, n_devices=1)
    assert read(w) is None
