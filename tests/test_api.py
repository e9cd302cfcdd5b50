"""Public API surface: top-level exports, signature snapshots, the
render-kwarg deprecation shims, and the default_config CMS-width formula
(pinning the docstring/code reconciliation)."""
import importlib
import inspect
import warnings

import numpy as np
import pytest

import repro
import repro.serve
from repro.core.pipeline import default_cms_cols
from repro.graph import mode_degree, planted_partition

# The stable surface promised by the API redesign: importing any of these
# from the top-level package must keep working.
STABLE_EXPORTS = [
    "biggraphvis",
    "default_config",
    "BGVConfig",
    "BGVResult",
    "render",
    "EdgeStore",
    "StreamConfig",
    "TileEngine",
]


def test_stable_exports_in_all():
    assert set(STABLE_EXPORTS) <= set(repro.__all__)


@pytest.mark.parametrize(
    "module",
    ["repro", "repro.serve", "repro.resilience", "repro.sharding", "repro.graph"],
)
def test_all_exports_resolve(module):
    mod = importlib.import_module(module)
    assert mod.__all__
    for name in mod.__all__:
        assert getattr(mod, name) is not None


def test_dir_includes_exports():
    assert set(repro.__all__) <= set(dir(repro))


def test_unknown_attribute_raises():
    with pytest.raises(AttributeError):
        repro.not_an_export


def test_lazy_exports_are_canonical_objects():
    # The lazy __getattr__ must hand out the same objects as the deep
    # module paths — not copies or wrappers.
    from repro.core.pipeline import BGVConfig, biggraphvis
    from repro.data.edge_store import EdgeStore
    from repro.serve.tiles import TileEngine

    assert repro.biggraphvis is biggraphvis
    assert repro.BGVConfig is BGVConfig
    assert repro.EdgeStore is EdgeStore
    assert repro.TileEngine is TileEngine
    assert repro.serve.TileEngine is TileEngine


def test_signature_snapshot():
    """Keyword-level compatibility snapshot of the stable entry points —
    renaming/removing a parameter is an API break and must show up here."""
    assert list(inspect.signature(repro.biggraphvis).parameters) == [
        "source", "n_nodes", "cfg", "stream", "put",
        "render_path", "render_cfg", "checkpoint", "resume",
    ]
    assert list(inspect.signature(repro.default_config).parameters) == [
        "n_nodes", "n_edges", "degree_threshold", "rounds", "iterations",
        "s_cap", "repulsion", "grid_size", "grid_window", "grid_rebuild",
        "stop_tolerance", "min_iterations", "init", "nan_guard",
    ]
    assert list(
        inspect.signature(repro.BGVResult.render).parameters
    ) == ["self", "path", "cfg"]


def test_default_cms_cols_formula():
    """Regression for the docstring/code mismatch: the implemented formula
    is max(256, |E| // 1000) — NOT the 1e-4·|E| the seed docstring
    claimed."""
    assert default_cms_cols(0) == 256
    assert default_cms_cols(255_999) == 256
    assert default_cms_cols(1_000_000) == 1000
    assert default_cms_cols(34_000_000) == 34_000  # paper-scale graph
    cfg = repro.default_config(1000, 2_000_000, 4)
    assert cfg.cms.cols == default_cms_cols(2_000_000) == 2000


@pytest.fixture(scope="module")
def tiny_scene():
    n = 120
    edges, _ = planted_partition(n, 4, 0.3, 0.01, seed=3)
    cfg = repro.default_config(
        n, len(edges), mode_degree(edges, n), iterations=5, s_cap=32
    )
    return edges, n, cfg


def test_render_method_replaces_kwargs(tiny_scene, tmp_path, monkeypatch):
    """BGVResult.render() is the entry point; the old render_path=/
    render_cfg= kwargs still work but warn exactly once per process."""
    import repro.core.pipeline as pipeline

    edges, n, cfg = tiny_scene
    res = repro.biggraphvis(edges, n, cfg)
    img, stats = res.render(str(tmp_path / "direct.png"))
    assert img.dtype == np.uint8 and img.ndim == 3
    assert (tmp_path / "direct.png").exists()
    assert res.timings["render_s"] > 0

    monkeypatch.setattr(pipeline, "_RENDER_KWARGS_WARNED", False)
    with pytest.warns(DeprecationWarning, match=r"\.render\(path"):
        repro.biggraphvis(
            edges, n, cfg, render_path=str(tmp_path / "shim.png")
        )
    assert (tmp_path / "shim.png").exists()

    # Second shim use in the same process: silent (warn-once).
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        repro.biggraphvis(
            edges, n, cfg, render_path=str(tmp_path / "shim2.png")
        )
    assert (tmp_path / "shim2.png").exists()


def test_shim_and_method_agree(tiny_scene, tmp_path, monkeypatch):
    import repro.core.pipeline as pipeline

    edges, n, cfg = tiny_scene
    monkeypatch.setattr(pipeline, "_RENDER_KWARGS_WARNED", True)
    res = repro.biggraphvis(
        edges, n, cfg, render_path=str(tmp_path / "a.png")
    )
    img_method, _ = res.render(str(tmp_path / "b.png"))
    a = (tmp_path / "a.png").read_bytes()
    b = (tmp_path / "b.png").read_bytes()
    assert a == b
    assert img_method.shape[2] == 3
