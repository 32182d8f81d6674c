"""Model description from a configuration file, and its weights from a seed.

The weights are made on the device in one jitted call, in the dtype they
are served in, laid out as the program's decoder-only LM takes them
(stacked blocks: every block leaf has a leading layer axis).  The same
function makes them again for the reference, so the reference never
takes a tensor from the program.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict

import numpy as np

from bench.traffic import seed_sequence

DTYPES = ("bfloat16", "float32")


@dataclasses.dataclass(frozen=True)
class Model:
    """A dense RoPE/GQA/SwiGLU decoder, in the published config's terms."""
    name: str
    arch: str
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    dtype: str

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def block_matmul_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        attn = 2 * d * self.num_attention_heads * hd \
            + 2 * d * self.num_key_value_heads * hd
        return attn + 3 * d * self.intermediate_size


def load_config(root: Path, entry: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file a ``BENCHMARK.json`` entry names."""
    path = Path(root) / entry["file"]
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file at {path}")
    return json.loads(path.read_text())


def model_of(conf: Dict[str, Any]) -> Model:
    if conf["torch_dtype"] not in DTYPES:
        raise ValueError(f"unsupported dtype {conf['torch_dtype']!r}")
    return Model(name=conf["name"], arch=conf["arch"],
                 hidden_size=conf["hidden_size"],
                 intermediate_size=conf["intermediate_size"],
                 num_attention_heads=conf["num_attention_heads"],
                 num_key_value_heads=conf["num_key_value_heads"],
                 num_hidden_layers=conf["num_hidden_layers"],
                 vocab_size=conf["vocab_size"],
                 rms_norm_eps=float(conf["rms_norm_eps"]),
                 rope_theta=float(conf["rope_theta"]),
                 dtype=conf["torch_dtype"])


def weight_key(seed: int):
    import jax
    words = seed_sequence(seed).spawn(2)[0].generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def make_params(model: Model, seed: int):
    """All weights, on the default device, in ``model.dtype``: dense
    kernels N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales 1."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(model.dtype)
    d, f, v, n = (model.hidden_size, model.intermediate_size,
                  model.vocab_size, model.num_hidden_layers)
    hq = model.num_attention_heads * model.head_dim
    hkv = model.num_key_value_heads * model.head_dim

    def build(key):
        names = ["emb", "head", "wq", "wk", "wv", "wo", "wi", "wg", "wf"]
        ks = dict(zip(names, jax.random.split(key, len(names))))

        def normal(name, shape, std):
            return (jax.random.normal(ks[name], shape, jnp.float32)
                    * std).astype(dt)

        def dense(name, d_in, d_out):
            return {"w": normal(name, (n, d_in, d_out), d_in ** -0.5)}

        ones = jnp.ones((n, d), dt)
        return {
            "embed": {"emb": normal("emb", (v, d), 0.02)},
            "blocks": {
                "ln1": {"scale": ones},
                "attn": {"wq": dense("wq", d, hq), "wk": dense("wk", d, hkv),
                         "wv": dense("wv", d, hkv), "wo": dense("wo", hq, d)},
                "ln2": {"scale": ones},
                "mlp": {"wi": dense("wi", d, f), "wg": dense("wg", d, f),
                        "wo": dense("wf", f, d)},
            },
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "lm_head": {"w": normal("head", (d, v), d ** -0.5)},
        }

    return jax.jit(build)(weight_key(seed))
