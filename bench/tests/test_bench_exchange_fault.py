"""The fault "vote exchange left out": on four virtual CPU devices, each
chip tallies its own votes alone (``packed_vote_allreduce`` returns the local
votes, patched before the engine is built) and the ``whype4-closed`` cell,
rehearsed, must read ``correct`` false. So the cell's ``correct`` compares
the exchanged tally and does not assume it."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import json
import os
import subprocess
import sys
import textwrap

import spec

CODE = """
import json, sys
sys.path[:0] = [{src!r}, {bench!r}]
from repro.distributed import collectives

def local_votes(votes, axis_name, **kw):
    return votes.astype("int32")

collectives.packed_vote_allreduce = local_votes
import run
r = run.run_cell("whype4-closed", 2**32 + 41, 0.5, False, rehearse=True)
print(json.dumps({{"correct": r["correct"],
                  "checks": {{k: v["value"] for k, v in r["checks"].items()}}}}))
"""


def test_vote_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CODE.format(src=os.path.join(spec.ROOT, "src"), bench=spec.BENCH_DIR)
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=spec.ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is False, r
    assert r["checks"]["pred_mismatch"] > 0, r
    assert r["checks"]["unanswered"] == 0 and r["checks"]["misrouted"] == 0
