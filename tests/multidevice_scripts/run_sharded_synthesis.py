"""Subprocess: mesh-aware synthesize()/backends on 8 forced host devices.

SYNTH_TP_OK     — xla backend with mesh (dp=4, tp=2): the [D+H, 4H] gate
                  contraction row-parallels over "model" (the compiled HLO
                  contains the gate-boundary all-reduce) and the outputs
                  match the single-device program to float tolerance (TP
                  changes the reduction order, so allclose, not bitwise).
SYNTH_PALLAS_OK — pallas backend under shard_map over "data": each shard
                  folds its local C-slow streams into its own kernel grid;
                  outputs match the unsharded fused kernel, also for a
                  stream count that does not divide the data axis.
SYNTH_CACHE_OK  — synthesize(mesh=...) forks the memo + ledger keys (no
                  aliasing against the single-device artifact).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.codegen import build_program, pallas_backend, xla_backend  # noqa: E402
from repro.core.synthesis import NetworkSpec, synthesize  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.obs import OBS  # noqa: E402

assert jax.device_count() == 8
mesh = make_local_mesh(dp=4, tp=2)

# lstm gate weight is [d_in + H, 4H] = [16, 32]: rows divide tp=2
spec = NetworkSpec(num_inputs=8, num_hidden_layers=2, nodes_per_layer=8,
                   num_outputs=4, cell="lstm", seq_len=6)
prog = build_program(spec)
params = prog.params
u = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8, 6, 8)))

base = jax.jit(xla_backend.compile_program(prog))
tp = jax.jit(xla_backend.compile_program(prog, mesh=mesh))
y0, y1 = np.asarray(base(params, u)), np.asarray(tp(params, u))
np.testing.assert_allclose(y1, y0, atol=1e-5)
hlo = jax.jit(xla_backend.compile_program(prog, mesh=mesh)) \
    .lower(params, u).compile().as_text()
assert "all-reduce" in hlo, "gate TP must lower to an all-reduce"
print("SYNTH_TP_OK")

# C-slow × data shards: 4 streams over dp=4, each shard folds locally
spec_c = NetworkSpec(num_inputs=8, num_hidden_layers=1, nodes_per_layer=8,
                     num_outputs=4, cell="lstm", seq_len=6, c_slow=4)
prog_c = build_program(spec_c)
uc = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (4, 8, 6, 8)))
p0 = pallas_backend.compile_program(prog_c)
p1 = pallas_backend.compile_program(prog_c, mesh=mesh)
yc0 = np.asarray(jax.jit(p0)(prog_c.params, uc))
yc1 = np.asarray(jax.jit(p1)(prog_c.params, uc))
np.testing.assert_allclose(yc1, yc0, atol=1e-5)
# a leading axis that does not divide dp=4 is zero-padded over all shards
yc3 = np.asarray(jax.jit(p1)(prog_c.params, uc[:3]))
np.testing.assert_allclose(yc3, yc0[:3], atol=1e-5)
print("SYNTH_PALLAS_OK")

r0 = synthesize(spec, batch=8, backend="xla", measure=False)
r1 = synthesize(spec, batch=8, backend="xla", mesh=mesh, measure=False)
assert not r0.cache_hit and not r1.cache_hit     # distinct memo keys
assert r1.backend == "xla" and r1.fallback_from is None
rows = OBS.ledger.report()
assert any(r["program"].endswith("|mesh4x2") for r in rows), \
    [r["program"] for r in rows]
print("SYNTH_CACHE_OK")
