"""CPU tests of the comparison that decides ``correct``, at a smoke size.

The benchmark's whole run (``run.run`` without its look for a chip) is
driven over a tiny bf16 configuration of the cells' own architecture:
served as the program serves it, the check passes; with a fault planted
under the timed path (a token altered where it is produced, the verify
step's cache left unwritten, half of each wave's answers dropped, the
drafts altered where the edge produces them), or with the reference put
in the program's place at the next precision down (the control), it
fails.  A one-chip cell has no exchange between
chips to leave out."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from bench import faults, reference, run

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.chat"
SEED = 2 ** 31 + 19


def _tiny_root(tmp: Path) -> Path:
    """A checkout with one extra cell: the deepseek-7b configuration at
    a smoke size (every other key as the cell runs it) under a short
    greedy mix of two prefill buckets, held to the chat cell's limits."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    conf = json.loads((ROOT / "bench/configs/deepseek-7b.json").read_text())
    conf.update(name="tiny", hidden_size=64, intermediate_size=172,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=3, vocab_size=512)
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    mix.update(prompt={"dist": "uniform", "min": 5, "max": 12},
               output={"dist": "uniform", "min": 3, "max": 10},
               pool=8, wave=4, check_tokens=160)
    (tmp / "bench/traffic/tiny.json").write_text(json.dumps(mix))
    shutil.copy(ROOT / "bench/limits/deepseek-7b.chat.json",
                tmp / f"bench/limits/{CELL}.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "x",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "x"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny"))


def _run(root, **kw):
    return run.run(root, CELL, SEED, 0.5, False, need_chip=False, **kw)


def test_served_streams_pass_and_the_control_fails(tiny):
    """The program's streams pass; the reference every part a precision
    step down, read at the positions of the same streams and held to the
    same limits, fails.  The cloud suffix and head alone at INT8 is read
    beside it, and reported with its verdict."""
    res = _run(tiny, controls=tuple(reference.CONTROLS))
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert set(res["controls"]) == set(reference.CONTROLS)
    down = res["controls"]["step_down"]
    assert down["correct"] is False
    assert any(c["value"] > c["limit"] for c in down["check"].values())
    for name in ("program", *reference.CONTROLS):
        assert set(res["readings"][name]) >= set(reference.NUMBERS)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_the_batch_left_out",
                                   "draft_altered"])
def test_a_broken_timed_path_fails_the_check(tiny, monkeypatch, fault):
    """Faults planted under the timed path (``bench/faults.py``), each
    read by the number that has to catch it."""
    number = faults.FAULTS[fault](monkeypatch.setattr)
    res = _run(tiny)
    assert res["correct"] is False
    got = res["check"][number]
    assert got["value"] > got["limit"]
