"""Subprocess: sharded train/serve step lowers+compiles on a (2,2,2) mesh,
and the sharded loss matches the single-device loss (SPMD correctness)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import numpy as np

from repro import optim
from repro.configs import get_smoke_config
from repro.launch import steps as steps_lib
from repro.models import lm
from repro.models.config import ShapeSpec

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)

for arch in ("smollm-135m", "olmoe-1b-7b", "zamba2-1.2b"):
    cfg = dataclasses.replace(get_smoke_config(arch), remat=False)
    shape = ShapeSpec("mini_train", seq_len=16, global_batch=8, kind="train")
    lowered = steps_lib.lower_cell(cfg, shape, mesh, optim.AdamWConfig())
    compiled = lowered.compile()

    # numeric parity: sharded step loss == unsharded step loss
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = optim.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    specs = steps_lib.input_specs(cfg, shape)
    sh = steps_lib.plan_shardings(cfg, shape, mesh, specs)
    params_sh = jax.device_put(params, sh["params"])
    opt_sh = jax.tree.map(jax.device_put, opt_state,
                          optim.AdamWState(sh["opt_state"].step, sh["opt_state"].m, sh["opt_state"].v))
    batch_sh = jax.device_put(batch, sh["batch"])

    step = steps_lib.make_train_step(cfg, optim.AdamWConfig())
    with mesh:
        _, _, m_sharded = jax.jit(
            step, in_shardings=(sh["params"], sh["opt_state"], sh["batch"])
        )(params_sh, opt_sh, batch_sh)
    _, _, m_single = jax.jit(step)(params, opt_state, batch)
    np.testing.assert_allclose(float(m_sharded["loss"]), float(m_single["loss"]),
                               rtol=2e-4)
    print(f"{arch}: sharded={float(m_sharded['loss']):.6f} "
          f"single={float(m_single['loss']):.6f}")

print("MINIDRYRUN_OK")
