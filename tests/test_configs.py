"""Config registry + per-arch smoke tests: every assigned architecture
instantiates (reduced config) and runs one real forward/train step on CPU
with finite outputs — the FULL configs are exercised only via the dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (REGISTRY, get_arch, input_specs, list_archs,
                           list_cells)
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_cell

jax.config.update("jax_platform_name", "cpu")

ASSIGNED = ["phi3-medium-14b", "deepseek-7b", "qwen3-moe-30b-a3b",
            "grok-1-314b", "flux-dev", "unet-sd15", "deit-b", "vit-s16",
            "vit-h14", "resnet-152"]


def test_registry_has_all_assigned_plus_baselines():
    archs = list_archs()
    for a in ASSIGNED:
        assert a in archs, a
    for b in ("alexnet", "vgg16", "resnet-18", "googlenet"):
        assert b in archs, b
    assert set(list_archs(assigned_only=True)) == set(ASSIGNED)


def test_exactly_40_cells():
    cells = list_cells()
    assert len(cells) == 40
    per_arch = {}
    for a, s in cells:
        per_arch.setdefault(a, []).append(s)
    assert all(len(v) == 4 for v in per_arch.items().__iter__().__next__()[1:2])
    for a, shapes in per_arch.items():
        assert len(shapes) == 4, (a, shapes)


def test_full_configs_match_assignment_numbers():
    phi3 = get_arch("phi3-medium-14b").full
    assert (phi3.n_layers, phi3.d_model, phi3.n_heads, phi3.n_kv,
            phi3.d_ff, phi3.vocab) == (40, 5120, 40, 10, 17920, 100352)
    qwen = get_arch("qwen3-moe-30b-a3b").full
    assert (qwen.moe.n_experts, qwen.moe.top_k, qwen.d_ff,
            qwen.vocab) == (128, 8, 768, 151936)
    grok = get_arch("grok-1-314b").full
    assert (grok.n_layers, grok.d_model, grok.moe.n_experts,
            grok.moe.top_k) == (64, 6144, 8, 2)
    flux = get_arch("flux-dev").full
    assert (flux.n_double, flux.n_single, flux.d_model,
            flux.n_heads) == (19, 38, 3072, 24)
    r152 = get_arch("resnet-152").full
    assert r152.depths == (3, 8, 36, 3)
    vith = get_arch("vit-h14").full
    assert (vith.n_layers, vith.d_model, vith.patch) == (32, 1280, 14)


def test_input_specs_cover_all_cells():
    for arch, shape in list_cells():
        specs = input_specs(arch, shape)
        assert specs, (arch, shape)
        for k, v in specs.items():
            assert isinstance(v, jax.ShapeDtypeStruct), (arch, shape, k)


@pytest.fixture(scope="module")
def host_mesh():
    return make_host_mesh()


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_train_step_runs_and_is_finite(arch, host_mesh):
    """One REAL reduced-config train/serve step per arch on CPU."""
    spec = get_arch(arch)
    shape = next(iter(spec.shapes))           # the family's train shape
    cell = build_cell(arch, shape, host_mesh, smoke=True)
    compiled = cell.lower().compile()

    # materialize concrete inputs from the abstract args
    rng = np.random.RandomState(0)

    def concretize(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            if jnp.issubdtype(x.dtype, jnp.integer):
                # < smallest smoke n_classes/vocab (OOB labels would
                # NaN-fill through take_along_axis)
                return jnp.asarray(
                    rng.randint(0, 8, x.shape).astype(x.dtype))
            # non-negative so optimizer second moments stay valid
            return jnp.asarray(
                np.abs(rng.randn(*x.shape)).astype(x.dtype) * 0.02)
        return x

    def init_like(tree):
        return jax.tree_util.tree_map(concretize, tree)

    with cell.mesh, jax.set_mesh(cell.mesh):
        concrete = jax.tree_util.tree_map(concretize, cell.args,
                                          is_leaf=lambda x: isinstance(
                                              x, jax.ShapeDtypeStruct))
        out = compiled(*concrete)
    flat = jax.tree_util.tree_leaves(out)
    for leaf in flat:
        assert bool(jnp.all(jnp.isfinite(
            leaf.astype(jnp.float32)))), (arch, shape)


def test_sources_are_recorded():
    for a in list_archs():
        assert get_arch(a).source, a
