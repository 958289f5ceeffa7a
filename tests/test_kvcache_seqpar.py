"""shard_map flash-decoding == single-device reference (run in a subprocess
with 8 faked devices so the XLA flag never leaks)."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.kvcache import paged

mesh = jax.make_mesh((2, 4), ("data", "model"))
B, Pn, page, KVH, hd, H = 4, 8, 16, 2, 32, 4
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, hd), jnp.float32) * 0.3
kn = jnp.asarray(rng.randn(B, KVH, hd), jnp.float32) * 0.3
vn = jnp.asarray(rng.randn(B, KVH, hd), jnp.float32) * 0.3
kp = jnp.asarray(rng.randn(B, Pn, page, KVH, hd), jnp.float32) * 0.3
vp = jnp.asarray(rng.randn(B, Pn, page, KVH, hd), jnp.float32) * 0.3
# non-identity page tables (as the allocator would hand out under churn)
pt = jnp.asarray([rng.permutation(Pn) for _ in range(B)], jnp.int32)
pos = jnp.asarray(rng.randint(10, Pn * page - 2, B), jnp.int32)

kp_r = paged.write_token(kp, kn, pt, pos)
vp_r = paged.write_token(vp, vn, pt, pos)
o_r = paged.attend(q, kp_r, vp_r, pt, pos + 1)

with jax.set_mesh(mesh):
    o_s, kp_s, vp_s = jax.jit(lambda *a: paged.write_attend_seqpar(*a))(
        q, kn, vn, kp, vp, pt, pos)
np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r), atol=3e-5,
                           rtol=3e-5)
np.testing.assert_array_equal(np.asarray(kp_s), np.asarray(kp_r))
np.testing.assert_array_equal(np.asarray(vp_s), np.asarray(vp_r))
# no-mesh fallback path agrees too
o_f, kp_f, vp_f = paged.write_attend_seqpar(q, kn, vn, kp, vp, pt, pos)
np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_r), atol=3e-5,
                           rtol=3e-5)
print("seqpar-ok")
"""


def test_seqpar_flash_decoding_matches_reference():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "seqpar-ok" in out.stdout
