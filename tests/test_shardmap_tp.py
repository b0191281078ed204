"""shard_map manual-TP block: numerics vs oracle vs pjit, and the explicit
collective schedule (exactly one all-reduce). Runs in a subprocess with 8
forced host devices so the main test process keeps its single-device view.
"""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.models.shardmap_tp import (
    count_collectives, make_tp_block, shard_tp_weights, tp_block_pjit,
    tp_block_reference,
)

mesh = make_mesh((8,), ("model",))
ks = jax.random.split(jax.random.PRNGKey(0), 3)
B, D, F = 4, 64, 256
x = jax.random.normal(ks[0], (B, D))
w_in = jax.random.normal(ks[1], (D, F)) * 0.1
w_out = jax.random.normal(ks[2], (F, D)) * 0.1

ref = tp_block_reference(x, w_in, w_out)

w_in_s, w_out_s = shard_tp_weights(mesh, w_in, w_out)
sm_block = make_tp_block(mesh)
out_sm = sm_block(x, w_in_s, w_out_s)
np.testing.assert_allclose(np.asarray(out_sm), np.asarray(ref),
                           atol=1e-5, rtol=1e-5)

pj_block = tp_block_pjit(mesh)
out_pj = pj_block(x, w_in, w_out)
np.testing.assert_allclose(np.asarray(out_pj), np.asarray(ref),
                           atol=1e-5, rtol=1e-5)

# schedule audit: the manual path emits EXACTLY one all-reduce, nothing else
comp = sm_block.lower(x, w_in_s, w_out_s).compile()
census = count_collectives(comp)
assert census["all-reduce"] == 1, census
assert census["all-gather"] == 0 and census["all-to-all"] == 0, census
print("SHARDMAP_TP_OK", census)
"""


def test_shardmap_tp_numerics_and_schedule():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600, cwd=".",
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "SHARDMAP_TP_OK" in res.stdout
