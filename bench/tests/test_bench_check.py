"""CPU tests of the comparison that decides ``correct``, at a smoke size.

The benchmark's whole run (``run.run`` without its look for a chip) is
driven over a tiny bf16 configuration of the cells' own architecture:
served as the program serves it, the check passes; with a fault planted
under the timed path (a token altered where it is produced, the verify
step's cache left unwritten, half of each wave's answers dropped, the
drafts altered where the edge produces them), or with the reference put
in the program's place at the next precision down (the control), it
fails.  A one-chip cell has no exchange between
chips to leave out.

The same run over a toy architecture that exists only as a new file of
its own shows that a configuration's weights and shapes reach both the
program and the reference through its architecture's file; and the
tiny cell's weights and readings are pinned bit for bit, so a change
to the harness that moves no arithmetic shows that it moved none."""
from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import faults, reference, run
from bench.weights import load_arch

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.chat"
SEED = 2 ** 31 + 19

# the tiny cell's weights at SEED, and its readings over one call
# (``seconds`` 0), with every control
TINY_WEIGHTS_SHA256 = \
    "ab632cbb26d96a310666363c552573b0917d93c66c5003b25266dbd98dbd38e7"
TINY_READINGS = {
    "program": {"failed_requests": 0.0,
                "draft_miss_share": 16.666666666666664,
                "max_logit_gap": 0.02492833137512207,
                "mean_logit_gap": 0.0009587819759662335,
                "argmax_miss_share": 3.8461538461538463},
    "step_down": {"max_logit_gap": 1.642519474029541,
                  "mean_logit_gap": 0.38637465697068435,
                  "argmax_miss_share": 61.53846153846154},
    "cloud_int8": {"max_logit_gap": 0.017110347747802734,
                   "mean_logit_gap": 0.0006580902979924128,
                   "argmax_miss_share": 3.8461538461538463}}

# the dense file with heads twice as wide as hidden / heads, and norm
# scales drawn from the seed
TOY = """

_dense_model_of, _dense_make_params = model_of, make_params


def model_of(conf):
    m = _dense_model_of(conf)
    return dataclasses.replace(
        m, head_dim=2 * m.hidden_size // m.num_attention_heads)


def make_params(model, seed):
    import jax
    import jax.numpy as jnp

    def redraw(params, key):
        def scale(key, a):
            return (1.0 + 0.1 * jax.random.normal(key, a.shape, jnp.float32)
                    ).astype(a.dtype)

        k1, k2, k3 = jax.random.split(key, 3)
        b = params["blocks"]
        return dict(params, final_norm={
            "scale": scale(k3, params["final_norm"]["scale"])}, blocks=dict(
                b, ln1={"scale": scale(k1, b["ln1"]["scale"])},
                ln2={"scale": scale(k2, b["ln2"]["scale"])}))

    key = jax.random.fold_in(weight_key(seed), 1)
    return jax.jit(redraw)(_dense_make_params(model, seed), key)
"""


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


def _run(root, cell=CELL, seconds=0.5, **kw):
    return run.run(root, cell, SEED, seconds, False, need_chip=False, **kw)


def _digest(params) -> str:
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _arch(root, name):
    conf = json.loads((root / f"bench/configs/{name}.json").read_text())
    arch = load_arch(root, conf)
    return arch, arch.model_of(conf)


def test_the_weights_of_a_seed_are_pinned(tiny):
    arch, model = _arch(tiny, "tiny")
    assert _digest(arch.make_params(model, SEED)) == TINY_WEIGHTS_SHA256


def test_the_readings_of_one_call_are_pinned(tiny):
    res = _run(tiny, seconds=0.0, controls=tuple(reference.CONTROLS))
    assert res["attempted"] == 4 and res["correct"] is True
    assert res["readings"] == TINY_READINGS


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The tiny checkout with one more cell, ``toy.chat``: the tiny
    configuration under ``"arch": "toy"``, whose file exists only here."""
    root = _tiny_root(tmp_path_factory.mktemp("toy"))
    conf = json.loads((root / "bench/configs/tiny.json").read_text())
    conf.update(name="toy", arch="toy")
    (root / "bench/configs/toy.json").write_text(json.dumps(conf))
    (root / "bench/archs/toy.py").write_text(
        (ROOT / "bench/archs/dense.py").read_text() + TOY)
    # the chat cell's limits, but the mean gap: sound runs of the toy read
    # 0.0015-0.0034 over three seeds, and a program served the dense
    # file's norm scales in place of the toy's 0.129-0.169
    limits = json.loads((root / f"bench/limits/{CELL}.json").read_text())
    (root / "bench/limits/toy.chat.json").write_text(
        json.dumps(dict(limits, mean_logit_gap=0.02)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][-1], name="toy",
                                 file="bench/configs/toy.json"))
    bench["workloads"].append(dict(bench["workloads"][-1], name="toy.chat",
                                   config="toy"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_new_architecture_is_served_and_checked_from_its_own_file(toy):
    """The toy file's wider heads and drawn norm scales reach the program
    and the reference alike: its served streams pass the check, and the
    control a precision step down fails it."""
    arch, model = _arch(toy, "toy")
    params = arch.make_params(model, SEED)
    assert model.head_dim == 32
    assert params["blocks"]["attn"]["wq"]["w"].shape == (3, 64, 128)
    assert float(np.std(np.asarray(params["blocks"]["ln1"]["scale"],
                                   np.float32))) > 0.05
    res = _run(toy, "toy.chat", controls=("step_down",))
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is True
    down = res["controls"]["step_down"]
    assert down["correct"] is False
    assert any(c["value"] > c["limit"] for c in down["check"].values())


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
