"""`chip_smoke.py` exercised off the chip (the chip run itself is sent
through the chip tool, see README "Running").

 - `--dry-run` passes in a subprocess at tiny size on CPU (Pallas in
   interpret mode; with the suite's eight virtual devices its multi-chip
   phase runs too) and says so in its banner; with
   `JAX_COMPILATION_CACHE_DIR` set the compile cache lands THERE and
   `<checkout>/.jax_cache` is not created by the run;
 - without the flag, off-TPU, it exits non-zero, names the platform and
   prints no result.

The dry run is a one-minute subprocess.  `tests/conftest.py` starts it
when collection ends (`start_dry_run`), beside the first test files, so
that minute stays out of the time-boxed tier-1 run; the test here only
collects the result (and starts the run itself when nobody did).
"""
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
REPO_CACHE = os.path.join(REPO, ".jax_cache")


class DryRun:
    """A started `chip_smoke.py --dry-run` with its compile cache pointed
    at a scratch directory; output goes to files so that a full pipe can
    never block the child."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chip_smoke_dry_")
        self.cache = os.path.join(self.dir, "jaxcache")
        self.had_repo_cache = os.path.exists(REPO_CACHE)
        self._out = open(os.path.join(self.dir, "stdout"), "w+")
        self._err = open(os.path.join(self.dir, "stderr"), "w+")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=self.cache)
        self.proc = subprocess.Popen(
            [sys.executable, SMOKE, "--dry-run"], env=env, cwd=REPO,
            stdout=self._out, stderr=self._err, text=True)

    def finish(self, timeout):
        """(returncode, stdout, stderr); kills the child on timeout."""
        try:
            rc = self.proc.wait(timeout=timeout)
        finally:
            self.close()
        self._out.seek(0)
        self._err.seek(0)
        return rc, self._out.read(), self._err.read()

    def close(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def cleanup(self):
        self.close()
        self._out.close()
        self._err.close()
        shutil.rmtree(self.dir, ignore_errors=True)


def start_dry_run() -> DryRun:
    return DryRun()


@pytest.fixture(scope="module")
def dry_run(request):
    """(run, returncode, stdout, stderr) of the one dry run both tests
    below read: conftest's, or one started here when nobody did."""
    run = getattr(request.config, "_chip_smoke_dry_run", None)
    own = run is None
    if own:
        run = start_dry_run()
    try:
        yield (run,) + run.finish(timeout=600)
    finally:
        if own:
            run.cleanup()


def test_dry_run_passes_and_cache_follows_env(dry_run):
    run, rc, out, err = dry_run
    assert rc == 0, (out[-3000:], err[-3000:])
    lines = out.splitlines()
    assert "DRY RUN" in lines[0]
    assert "=== DRY RUN passed ===" in lines[-1]
    assert f"compile_cache: dir={run.cache}" in out
    assert '"claim": null}' in out
    # the dry run leaves no device record a driver could mistake for
    # a chip result
    assert '{"ok": true, "device"' not in out
    assert os.path.isdir(run.cache) and os.listdir(run.cache), \
        "nothing was cached under JAX_COMPILATION_CACHE_DIR"
    assert os.path.exists(REPO_CACHE) == run.had_repo_cache, \
        "the run created <checkout>/.jax_cache despite the env setting"


def test_dry_run_allows_no_fallback(dry_run):
    # the smoke tolerates no `fallback.*` event, and its three training
    # phases (wave, leafwise, data-parallel) and serving emit none
    import chip_smoke
    assert chip_smoke.ALLOWED_FALLBACKS == {}
    _, rc, out, err = dry_run
    assert rc == 0, (out[-3000:], err[-3000:])
    assert "\nfallback.events=0\n" in out
    assert "\n  fallback." not in out
    assert '"fallbacks": []' in out


def test_refuses_without_tpu():
    r = subprocess.run([sys.executable, SMOKE], cwd=REPO, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True)
    assert r.returncode not in (0, None)
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr
    assert r.stdout.strip() == "", "a result was printed without a TPU"
