"""A CPU rehearsal of every cell at its configuration's toy sizes, with the
kernels in interpret mode: each runs end to end, its answers equal the
plain reference's, and it then refuses to print a result without a TPU."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import json
import os
import subprocess
import sys

import pytest

import spec

BENCH = spec.load_benchmark()
RUN = os.path.join(spec.BENCH_DIR, "run.py")
SEED = 2**31 + 977


def _run(args, chips=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return subprocess.run([sys.executable, RUN, *args], capture_output=True,
                          text=True, env=env, timeout=timeout,
                          cwd=spec.ROOT)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_rehearsal_runs_end_to_end_then_refuses(cell):
    trace = "1" if cell["name"] == "table1-poisson" else "0"
    p = _run(["--workload", cell["name"], "--seed", str(SEED), "--seconds",
              "0.5", "--trace", trace, "--rehearse"], chips=cell["chips"])
    assert p.returncode == 3, p.stderr[-3000:]
    assert _no_result(p.stdout)
    err = p.stderr.splitlines()
    for name in ("pred_mismatch", "maxsim_mismatch", "unanswered", "misrouted"):
        assert f"check {name}: 0 (limit 0)" in err, p.stderr[-3000:]
    gap = [line for line in err if line.startswith("check link_ber_gap: ")]
    assert len(gap) == 1 and float(gap[0].split()[2]) < 7e-4, p.stderr[-3000:]
    assert any("0 compilations inside it" in line for line in err)
    assert "rehearsal: not a chip run, no result" in err


def test_refuses_without_a_tpu():
    p = _run(["--workload", "table1-closed", "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_refuses_with_fewer_chips_than_the_cell_asks_for():
    import run

    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.check_devices(4, rehearse=True)
