"""The four-chip cell's configuration form (placement by bytes, packed
workers, the sharded metrics) runs end to end on the CPU."""
import os
import subprocess
import sys

from conftest import BENCH, ROOT


def test_a_bytes_balanced_configuration_serves_exactly():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    p = subprocess.run([sys.executable,
                        str(BENCH / "tests" / "sharded_bytes_check.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    assert "SHARDED-BYTES-OK" in p.stdout
