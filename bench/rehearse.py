#!/usr/bin/env python3
"""Compile a cell's device programs for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell>

Compiles the weights program (every int4 leaf from the seed), the
engine's `(max_batch, prefill_chunk)` prefill step and its
`(max_batch, 1)` decode step at the cell's real sizes, and prints each
program's `memory_analysis()` and whether the served kernels are in it.
The TPU's compiler refuses here what it would refuse on the chip: a
program that does not fit, a block it cannot tile.  It counts one
program at a time, not what the process keeps besides.
"""
import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(HERE), "src"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

KERNELS = ("paged_flash_attention", "swiglu_qgemv", "cim_gemv")


def _analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    text = compiled.as_text()
    out["kernels"] = {k: sum(1 for line in text.splitlines()
                             if "tpu_custom_call" in line and k in line)
                      for k in KERNELS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cells
    import program
    from repro.kernels import ops
    from repro.models import DecoderLM
    from repro.models.common import spec_structs

    jax.config.update("jax_enable_compilation_cache", False)
    # the code asks the (CPU) backend which route to take: steer it to
    # the TPU route the described chip compiles
    ops._interpret = lambda: False
    cell = cells.workload(args.workload)
    cfg = cells.config(cell["config"])
    fam = cells.family(cfg)
    geom = cells.traffic(cell["traffic"])["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def put(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    out = {}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    make = jax.jit(functools.partial(fam.all_leaves, cfg))
    out["weights"] = _analysis(make.lower(key).compile())
    leaves = jax.eval_shape(functools.partial(fam.all_leaves, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    model = DecoderLM(fam.model_config(cfg))
    params = put(program.param_tree(cfg, leaves, model))
    b, ps = geom["max_batch"], geom["page_size"]
    n_pages = b * geom["max_seq"] // ps
    kv = {"int8": jnp.int8, "bf16": jnp.bfloat16}[cfg["kv_dtype"]]
    state = put(spec_structs(model.decode_state_specs(
        b, n_pages, ps, kv)["paged"]))

    def i32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    step = jax.jit(model.serve_step, donate_argnums=(1,))
    for name, s in (("prefill", geom["prefill_chunk"]), ("decode", 1)):
        compiled = step.lower(params, state, {"tokens": i32((b, s))},
                              i32((b, geom["max_seq"] // ps)), i32((b,)),
                              i32((b,))).compile()
        out[name] = _analysis(compiled)
    print(json.dumps({"workload": args.workload, "programs": out}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
