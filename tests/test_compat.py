"""repro.compat — the jax choke point: monkeypatched resolvers, a real
single-device shard_map through the wrapper, the Auto-axis mesh and the
compile-cache helper."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat


# ---------------------------------------------------------------- make_mesh
@pytest.mark.parametrize("shape,names", [((1,), ("data",)),
                                         ((1, 1), ("data", "model"))])
def test_make_mesh_axes_are_auto(shape, names):
    """jax 0.9.0's make_mesh defaults to Explicit axes; the sharded scans
    are written for Auto ones, so compat.make_mesh asks for Auto on every
    axis."""
    from jax.sharding import AxisType
    mesh = compat.make_mesh(shape, names)
    assert mesh.axis_names == names
    assert tuple(mesh.axis_types) == (AxisType.Auto,) * len(names)


def test_make_mesh_passes_devices():
    dev = jax.devices()[:1]
    mesh = compat.make_mesh((1,), ("data",), devices=dev)
    assert list(mesh.devices.flat) == dev


def test_raw_make_mesh_is_a_drifted_surface():
    """The source linter flags a raw jax.make_mesh outside compat.py."""
    from repro.analysis.source_lint import DRIFTED_SUFFIXES
    assert "jax.make_mesh" in DRIFTED_SUFFIXES


# ---------------------------------------------------------------- shard_map
def _api_fake(seen):
    """jax.shard_map's keyword surface: the flag is named ``check_vma``."""
    def fake(f, *, mesh, in_specs, out_specs, check_vma=True):
        seen.update(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=check_vma)
        return f
    return fake


def test_shard_map_passes_check_vma_on_new_api(monkeypatch):
    seen = {}
    monkeypatch.setattr(compat, "_resolve_shard_map",
                        lambda: _api_fake(seen))
    f = lambda x: x  # noqa: E731
    out = compat.shard_map(f, mesh="m", in_specs=P(),
                           out_specs=P(), check_vma=False)
    assert out is f
    assert seen["check_vma"] is False
    assert seen["mesh"] == "m"


def test_shard_map_none_leaves_library_default(monkeypatch):
    seen = {}
    monkeypatch.setattr(compat, "_resolve_shard_map",
                        lambda: _api_fake(seen))
    compat.shard_map(lambda x: x, mesh="m", in_specs=P(), out_specs=P())
    assert seen["check_vma"] is True          # untouched default


def test_shard_map_real_single_device():
    """The wrapper drives the installed jax end-to-end on a 1-device mesh."""
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(
        lambda x: jax.lax.psum(x.sum(), "data")[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
    out = jax.jit(fn)(jnp.arange(8, dtype=jnp.float32))
    assert float(np.asarray(out)[0]) == 28.0


# ----------------------------------------------------------------- set_mesh
def test_set_mesh_prefers_jax_set_mesh(monkeypatch):
    calls = []

    @contextlib.contextmanager
    def fake_set_mesh(mesh):
        calls.append(("enter", mesh))
        yield mesh
        calls.append(("exit", mesh))

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
    with compat.set_mesh("MESH") as m:
        assert m == "MESH"
        assert calls == [("enter", "MESH")]
    assert calls == [("enter", "MESH"), ("exit", "MESH")]


# ------------------------------------------------------------ cost analysis
def test_normalize_cost_analysis_shapes():
    assert compat.normalize_cost_analysis(None) == {}
    assert compat.normalize_cost_analysis({"flops": 2.0}) == {"flops": 2.0}


def test_cost_analysis_dict_real_compiled():
    c = jax.jit(lambda a, b: (a @ b).sum()).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    ca = compat.cost_analysis_dict(c)
    assert isinstance(ca, dict)
    assert ca["flops"] > 0


# ------------------------------------------------------------- environment
def test_jax_api_report_and_missing():
    r = compat.jax_api_report()
    assert r["jax_version"] == jax.__version__
    assert r["shard_map"] is True
    assert compat.missing_apis() == []


def test_installed_jax_is_the_supported_one():
    """One JAX: the tests, CI and the chip all run compat.SUPPORTED_JAX."""
    assert jax.__version__ == compat.SUPPORTED_JAX


@pytest.mark.parametrize("env", [None, "custom"])
def test_configure_compile_cache_path(monkeypatch, tmp_path, env):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing; without
    it the cache goes to <repo>/.jax_cache. jax.config.update is recorded,
    not applied, so the test process keeps the cache off."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compat.configure_compile_cache()
        assert path == str(compat.REPO_ROOT / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        assert compat.configure_compile_cache() == want
        assert calls == []


def test_resolve_shard_map_matches_installed_jax():
    fn = compat._resolve_shard_map()
    assert callable(fn)
    import inspect
    assert "check_vma" in inspect.signature(fn).parameters
