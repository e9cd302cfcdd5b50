"""A configuration, a mix, a driver, a limit file and metrics added as files
are found by name, and a run without a TPU prints no result."""
import json
import os
import subprocess
import sys
import textwrap

from bench import harness

DRIVER = '''
class Driver:
    def __init__(self, wl, cfg, mix, seed):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.attempted = self.failed = 0
        self.errors = []

    def setup(self):
        self.value = self.cfg["size"] * self.mix["rate"]

    def window(self, seconds):
        self.attempted = 3
        return {"dummy_s": self.value + self.seed}

    def release(self):
        pass

    def check(self, limits):
        return {"dummy_gap": {"value": 0.5, "limit": limits["dummy_gap"]}}

    def layer_context(self):
        return {"value": self.value}
'''


def write(root, rel, text):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def registry(tmp_path):
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "dummy", "source": "https://example.org",
                     "file": "bench/configs/dummy.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "dummy.cell", "config": "dummy",
                       "traffic": "dummy", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "dummy_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "dummy.metric", "unit": "s", "better": "lower",
             "source": "program_counter", "layer": "dummy", "moves": "dummy_s",
             "workloads": ["dummy.cell"]},
            {"name": "silent.metric", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "dummy", "moves": "dummy_s",
             "workloads": ["dummy.cell"]}],
    }
    write(tmp_path, "BENCHMARK.json", json.dumps(bench))
    bench_dir = tmp_path / "bench"
    write(bench_dir, "configs/dummy.json", json.dumps({"size": 2}))
    write(bench_dir, "traffic/dummy.json", json.dumps({"driver": "dummy", "rate": 3}))
    write(bench_dir, "limits/dummy.cell.json", json.dumps({"dummy_gap": 1.0}))
    write(bench_dir, "drivers/dummy.py", textwrap.dedent(DRIVER))
    write(bench_dir, "metrics/dummy.metric.py",
          "def read(ctx):\n    return ctx['value'] / 2\n")
    write(bench_dir, "metrics/silent.metric.py", "def read(ctx):\n    return None\n")
    return bench_dir


def run_dummy(monkeypatch, tmp_path, capsys, trace):
    import bench.run as run

    bench_dir = registry(tmp_path)
    monkeypatch.setattr(harness, "BENCH", bench_dir)
    monkeypatch.setattr(harness, "CHECKOUT", tmp_path)
    monkeypatch.setattr(harness, "peaks", lambda kind: {})
    monkeypatch.setattr(run, "accelerator_ok", lambda wl: True)
    rc = run.main(["--workload", "dummy.cell", "--seed", "4", "--seconds", "1",
                   "--trace", str(trace)])
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_dummy_cell_end_to_end_metrics_found_by_name(monkeypatch, tmp_path, capsys):
    rc, res = run_dummy(monkeypatch, tmp_path, capsys, trace=0)
    assert rc == 0
    assert res["correct"] is True and res["attempted"] == 3
    assert res["metrics"]["dummy_s"] == {"value": 10, "unit": "s"}
    assert res["metrics"]["setup_s"]["unit"] == "s"
    assert list(res)[-1] == "checks"
    assert res["checks"] == {"dummy_gap": {"value": 0.5, "limit": 1.0}}


def test_dummy_metric_found_by_name_and_silent_metric_left_out(monkeypatch, tmp_path, capsys):
    rc, res = run_dummy(monkeypatch, tmp_path, capsys, trace=1)
    assert rc == 0
    assert res["metrics"] == {"dummy.metric": {"value": 3.0, "unit": "s"}}


def test_unknown_device_kind_has_no_peaks():
    import pytest

    with pytest.raises(KeyError):
        harness.peaks("cpu")
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "berkstan.batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr
