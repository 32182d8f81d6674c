"""A configuration file, its architecture's file, and the key its weights
are drawn from.

A configuration names its architecture by ``arch``; the harness finds
everything that knows that architecture's shapes in
``bench/archs/<arch>.py`` (``load_arch``), so a new architecture is added
as a new file.  Each such file provides:

* ``Model``: a frozen, hashable dataclass of the sizes it serves.  The
  harness reads ``name``, ``vocab_size``, ``num_hidden_layers``,
  ``dtype`` and (for the final norm) ``rms_norm_eps`` from it;
* ``model_of(conf) -> Model``, which refuses, naming the key, what the
  file does not serve as stated;
* ``make_params(model, seed)``: every weight, in one jitted call on the
  device keyed by ``weight_key(seed)``, laid out as the program's
  decoder-only LM takes them (``embed``, ``blocks`` stacked over layers,
  ``final_norm``, ``lm_head``).  The same function makes them again for
  the reference, so the reference never takes a tensor from the program;
* ``engine_config(model, cfg_cls)``: the program's config (``LMConfig``);
* ``request_flops(model, prompt_len, served)``: the model FLOPs of one
  served request;
* ``block(model, bp, x, pos, lat, kv)``: the reference's math for one
  block (``bench/reference.py`` says what ``lat`` and ``kv`` do).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

import numpy as np

from bench.traffic import seed_sequence

DTYPES = ("bfloat16", "float32")


def load_config(root: Path, entry: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file a ``BENCHMARK.json`` entry names."""
    path = Path(root) / entry["file"]
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file at {path}")
    return json.loads(path.read_text())


def load_arch(root: Path, conf: Dict[str, Any]) -> ModuleType:
    """``bench/archs/<arch>.py`` of the configuration ``conf``."""
    path = Path(root) / "bench" / "archs" / f"{conf['arch']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no architecture file for {conf['arch']!r} at {path}")
    return module_at(path)


def module_at(path: Path) -> ModuleType:
    """The Python file at ``path``, loaded once per process: an
    architecture's Model class, and the reference compiled for it, are
    then the same objects in every run."""
    return _module_at(Path(path).resolve())


@functools.lru_cache(maxsize=None)
def _module_at(path: Path) -> ModuleType:
    # registered under a name made from its path, since a dataclass
    # looks its module up in sys.modules
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def weight_key(seed: int):
    import jax
    words = seed_sequence(seed).spawn(2)[0].generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")
