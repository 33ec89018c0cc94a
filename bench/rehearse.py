#!/usr/bin/env python3
"""Compile a configuration's served programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config mamba1-falcon7b [--slots 64] [--prompt 2048]

Lowers the server's decode block (K-step scan over ``lm.decode_step`` at the
cell's slot count) and one one-shot prefill of ``--prompt`` tokens, exactly
as ``DecodeServer`` builds them, against abstract weights on one device of a
described ``v5e:2x2`` topology, and prints each program's
``memory_analysis()``.  What the chip's compiler would refuse (memory,
tiling) it refuses here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--prompt", type=int, default=2048)
    args = ap.parse_args()

    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.models import lm
    from repro.runtime import DecodeServer

    jax.config.update("jax_enable_compilation_cache", False)
    cfgjson = harness.read_json(ROOT / "bench" / "configs" / f"{args.config}.json")
    model = harness.load_module(ROOT / "bench" / "configs" / f"{args.config}.py")
    cell = harness.Cell(args.config, 1, cfgjson, model, {}, {}, [], [])
    cfg = harness.model_config(cell)
    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    weights = on_chip(jax.eval_shape(
        functools.partial(model.init_weights, cfgjson["sizes"]), jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: lm.init_cache(cfg, args.slots, 8)))
    vec = lambda dt: jax.ShapeDtypeStruct((args.slots,), dt, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)

    # the server's own block program, built without its (host) state
    block = DecodeServer._make_block_fn(
        type("S", (), {"cfg": cfg, "S": cfgjson["server"]["max_seq"],
                       "eos_id": cfgjson["server"]["eos_id"]})(),
        cfgjson["server"]["block_k"])
    progs = {
        f"decode_block_k{cfgjson['server']['block_k']}_slots{args.slots}": (
            block, (weights, caches, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                    vec(jnp.int32), vec(jnp.float32), key)),
        f"prefill_T{args.prompt}": (
            jax.jit(lambda p, t: lm.prefill(p, cfg, t)),
            (weights, jax.ShapeDtypeStruct((1, args.prompt), jnp.int32, sharding=one))),
    }
    report = {}
    for name, (fn, a) in progs.items():
        t = time.perf_counter()
        compiled = fn.lower(*a).compile()
        ma = compiled.memory_analysis()
        report[name] = {
            "compile_s": round(time.perf_counter() - t, 3),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        }
        print(json.dumps({name: report[name]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
