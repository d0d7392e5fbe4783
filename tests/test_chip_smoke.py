"""chip_smoke.py cannot rot between chip runs: tier-1 drives the script
itself, here on the CPU.

  * no TPU visible -> non-zero exit naming the platform it found, and
    no summary line (the driver's first check of every PR);
  * alone in a directory -> the same, without importing anything;
  * `--cpu-dry-run` walks the `lanes` and `bytes` legs' whole control
    flow at toy sizes and says so (`"dry_run": true`);
  * the package's one JAX configuration site places the compile cache
    as chip_smoke's legs rely on (subprocesses: JAX config is
    per-process).

The `standalone` and `cluster` legs boot brokers; the suites that
already do that (test_standalone, test_kafka_e2e) cover those paths,
and a chip run covers the legs themselves.
"""

import json
import os
import shutil
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(args, tmp_path, cwd=REPO_ROOT, script=SMOKE, env=None):
    return subprocess.run(
        [sys.executable, script, *args,
         "--data-dir", str(tmp_path / "data"),
         "--out-dir", str(tmp_path / "out")],
        capture_output=True, text=True, cwd=cwd, timeout=300,
        env=env or dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_refuses_to_run_without_a_tpu(tmp_path):
    out = _run([], tmp_path)
    assert out.returncode not in (0, 1), out.stderr[-2000:]
    assert "default backend is 'cpu'" in out.stderr
    assert "no summary is printed" in out.stderr
    assert out.stdout.strip() == "", out.stdout


def test_refuses_outside_a_checkout(tmp_path):
    lonely = tmp_path / "lonely"
    lonely.mkdir()
    shutil.copy(SMOKE, lonely / "chip_smoke.py")
    out = _run([], tmp_path, cwd=str(lonely),
               script=str(lonely / "chip_smoke.py"))
    assert out.returncode not in (0, 1), out.stderr[-2000:]
    assert "not a checkout" in out.stderr
    assert out.stdout.strip() == "", out.stdout


def test_cpu_dry_run_walks_lanes_and_bytes(tmp_path):
    out = _run(["--cpu-dry-run", "--legs", "lanes,bytes"], tmp_path)
    assert out.returncode == 0, out.stderr[-4000:]
    summary_line, verdict_line = out.stdout.strip().splitlines()[-2:]
    # the last line is the verdict alone, with exactly these keys
    verdict = json.loads(verdict_line)
    assert set(verdict) == {"ok", "device"}, verdict
    assert set(verdict["device"]) == {"platform", "kind", "count"}, verdict
    assert verdict["ok"] is True
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    summary = json.loads(summary_line)
    assert summary["device"] == verdict["device"]
    assert summary["ok"] is True and summary["dry_run"] is True
    assert summary["claim"] is None
    assert summary["device"]["platform"] == "cpu"
    assert summary["data_dir"]["fstype"] not in ("tmpfs", "ramfs")
    assert set(summary["legs"]) == {"lanes", "bytes"}
    for name, leg in summary["legs"].items():
        assert leg["ok"] is True, (name, leg)
        assert leg["compile"]["programs"] > 0, (name, leg)
        assert leg["transfer_bytes"]["h2d"] > 0, (name, leg)
    assert summary["legs"]["lanes"]["dispatches"]["quorum.heartbeat_tick"] > 0
    # every cut of scale is named, with both sizes
    assert any(r.startswith("groups 50000 ->") for r in summary["reduced"])
    assert any(r.startswith("rows 256 ->") for r in summary["reduced"])
    # the full per-leg detail went to the out dir, not the summary line
    with open(tmp_path / "out" / "chip_smoke.json") as f:
        full = json.load(f)
    assert set(full["legs"]["bytes"]["kernels"]) == {
        "crc32c", "lz4", "snappy", "zstd_encode", "zstd_decode",
        "fused_crc_lz4", "fused_crc_snappy", "fused_crc_zstd",
    }


def _cache_dir_seen_by(env_overrides: dict) -> str:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_overrides)
    out = subprocess.run(
        [sys.executable, "-c",
         "import redpanda_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_placement(tmp_path):
    # placed from outside: the package sets no directory at all
    outside = str(tmp_path / "cache")
    assert _cache_dir_seen_by({"JAX_COMPILATION_CACHE_DIR": outside}) == outside
    # not placed: the fixed in-checkout path (no tempfile, pid or clock)
    assert _cache_dir_seen_by({}) == os.path.join(REPO_ROOT, ".jax_cache")
    # a process pinned to the CPU gets none
    assert _cache_dir_seen_by({"JAX_PLATFORMS": "cpu"}) == "None"
