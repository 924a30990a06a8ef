"""The runner the ``tests/test_examples*.py`` files share: one example
script as a subprocess on the CPU, its exit code asserted."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))


def _run(script, *argv, timeout=240):
    p = subprocess.run([sys.executable, os.path.join(REPO, script),
                        *argv],
                       capture_output=True, text=True, env=ENV,
                       timeout=timeout)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    return p
