"""The runner's refusals: no chip, and a directory without the program."""
import json
import os
import shutil
import subprocess
import sys

from perfbench import manifest, run


def test_exits_2_off_tpu_before_measuring_anything(capsys):
    cell = manifest.benchmark()["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""                    # no result line
    assert "nothing was run" in out.err


def test_exits_nonzero_where_only_the_benchmark_is_there(tmp_path):
    """A directory with BENCHMARK.json and the files under `paths` only."""
    b = manifest.benchmark()
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for p in b["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable if w == "python3" else w for w in b["command"]]
    r = subprocess.run(
        cmd + ["--workload", b["workloads"][0]["name"], "--seed", "1",
               "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode not in (0, None)
    assert r.stdout.strip() == ""
    assert "nothing was run" in r.stderr


def test_the_command_names_nothing_outside_paths():
    b = manifest.benchmark()
    for w in b["command"]:
        assert not w.startswith("/") and ".." not in w
    assert b["command"][-1] == "perfbench.run"
    assert "perfbench" in b["paths"]
    json.dumps(b)
