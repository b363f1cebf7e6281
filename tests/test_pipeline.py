"""GPipe-style pipeline parallelism: subprocess with 4 fake devices."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.launch.pipeline import pipeline_apply

mesh = make_mesh((4,), ('pipe',))
rng = np.random.RandomState(0)
S, MB, D = 4, 8, 16
ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.3)
xs = jnp.asarray(rng.randn(6, MB, D).astype(np.float32))  # 6 microbatches

def layer_fn(p, x):
    return jnp.tanh(x @ p['w'])

out = pipeline_apply(layer_fn, {'w': ws}, xs, mesh, axis='pipe')
# reference: sequential through all 4 stages
ref = xs
for i in range(S):
    ref = jnp.tanh(ref @ ws[i])
err = float(jnp.max(jnp.abs(out - ref)))
print('ERR', err)
assert err < 1e-5, err
print('PIPELINE OK')
"""


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", CODE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PIPELINE OK" in r.stdout
