"""int8 compressed cross-pod gradient sum vs exact psum (subprocess mesh)."""
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as mesh_lib
from repro.optim.compress import int8_psum

mesh = mesh_lib.make_mesh((2, 4), ("pod", "data"))
g = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32), jnp.float32)

def body(gl):
    return int8_psum(gl[0], "pod")

f = jax.shard_map(body, mesh=mesh, in_specs=P("pod", None, None),
                  out_specs=P(None, None), check_vma=False)
got = np.asarray(jax.jit(f)(g))
want = np.asarray(g.sum(0))
err = np.abs(got - want).max()
tol = 2 * (np.abs(np.asarray(g)).max(axis=(0, 2), keepdims=False).max() / 127)
print("ERR", err, "TOL", tol)
assert err <= tol, (err, tol)
print("COMPRESS_OK")
"""


def test_int8_psum_matches_exact(tmp_path):
    script = tmp_path / "compress.py"
    script.write_text(SCRIPT)
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(repo))
    assert r.returncode == 0 and "COMPRESS_OK" in r.stdout, \
        f"stdout:\n{r.stdout[-1500:]}\nstderr:\n{r.stderr[-2500:]}"
