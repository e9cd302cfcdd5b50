"""Integration tests for the launchers: each ``main()`` driven through
``sys.argv`` at a tiny size, plus the layout CLI's edge loader."""
import sys

import jax
import pytest

from repro.launch import layout, render_runner, serve, stream_runner
from repro.launch.layout import load_edges

_CACHE_FLOORS = (
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_min_compile_time_secs",
)


@pytest.fixture
def run_main(tmp_path, monkeypatch, capsys):
    """Run a launcher's ``main()`` with ``argv`` and return its stdout.
    ``JAX_COMPILATION_CACHE_DIR`` names ``tmp_path``, so the launcher sets
    no cache directory in the checkout; the cache floors it lowers are
    restored afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    saved = {k: getattr(jax.config, k) for k in _CACHE_FLOORS}

    def run(module, *argv):
        monkeypatch.setattr(sys, "argv", [module.__name__, *map(str, argv)])
        module.main()
        return capsys.readouterr().out

    yield run
    for k, v in saved.items():
        jax.config.update(k, v)


def test_stream_runner_main(run_main):
    out = run_main(
        stream_runner, "--nodes", 400, "--communities", 8, "--chunk", 512,
        "--block-size", 256, "--rounds", 2, "--iterations", 3,
    )
    assert "streamed == one-shot: True" in out
    assert "passes=" in out and "peak device bytes" in out


def test_render_runner_main(run_main, tmp_path):
    png = tmp_path / "g.png"
    out = run_main(
        render_runner, "--nodes", 300, "--communities", 6, "--width", 64,
        "--height", 64, "--rounds", 2, "--iterations", 3, "--out", png,
    )
    assert png.read_bytes()[:8] == b"\x89PNG\r\n\x1a\n"
    assert f"wrote {png}" in out and "throughput:" in out


def test_serve_main(run_main):
    out = run_main(
        serve, "--nodes", 300, "--communities", 6, "--depth", 2,
        "--tile-size", 32, "--iterations", 3, "--requests", 20,
        "--slots", 4,
    )
    assert "served 20 requests" in out
    assert "steady-state recompiles: 0" in out


def test_layout_main(run_main, tmp_path):
    svg = tmp_path / "l.svg"
    run_main(
        layout, "--edges", "synthetic:300:6", "--out", svg, "--rounds", 2,
        "--iterations", 3,
    )
    assert svg.read_text().startswith("<svg")
    rows = (tmp_path / "l.csv").read_text().splitlines()
    assert rows[0] == "community,size,x,y,color_group"
    assert len(rows) > 1


def test_layout_cli_loaders(tmp_path):
    edges, n = load_edges("synthetic:200:4")
    assert n == 200 and len(edges) > 100
    # SNAP-format file with comments and sparse ids
    p = tmp_path / "g.txt"
    p.write_text("# comment\n10 20\n20 30\n10 30\n40 10\n")
    edges, n = load_edges(str(p))
    assert n == 4  # compacted ids
    assert len(edges) == 4
    assert edges.max() < 4
