"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell>

Lowers and compiles, at the cell's shapes, every program its window runs:
the prefill of each prompt bucket at the cell's prefill batch, the slot
insert, the decode segment and the release.  Nothing runs; the TPU
compiler refuses here what the chip's would (a program that does not fit,
a kernel off the tiling), and ``memory_analysis()`` gives each program's
argument, output and scratch bytes.  One JSON line per program.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def programs(cell, topo_device):
    """(name, jitted fn, abstract args, static kwargs) of the window."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.models import registry
    from repro.models.param import is_spec
    from repro.serving import ServingEngine

    from bench import spec, traffic

    one = SingleDeviceSharding(topo_device)
    cfg = spec.program_config(cell.config)
    k = cell.knobs["serving"]

    def sds(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(tuple(s.shape), jnp.dtype(s.dtype),
                                           sharding=one), tree,
            is_leaf=lambda x: is_spec(x) or isinstance(x, jax.ShapeDtypeStruct))

    params = sds(registry.abstract_params(cfg))
    eng = ServingEngine(cfg, params=params, max_len=k["max_len"])
    state = sds(jax.eval_shape(lambda: eng.init_slots(k["slots"])))
    cache1 = sds(registry.abstract_cache(cfg, 1, k["max_len"]))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    logits1 = jax.ShapeDtypeStruct((cfg.vocab_padded,), jnp.float32,
                                   sharding=one)
    for b in traffic.buckets(cell.mix):
        toks = jax.ShapeDtypeStruct((k["prefill_batch"], b), jnp.int32,
                                    sharding=one)
        yield f"prefill_{b}", eng._prefill, (params, {"tokens": toks}), {}
    yield ("insert", eng._insert,
           (state, cache1, logits1, i32, i32, i32, i32, f32), {})
    yield "segment", eng._segment, (params, state), {"k": k["segment"]}
    yield "release", eng._release, (state, i32), {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench import spec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(ROOT, args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name, fn, a, kw in programs(cell, topo.devices[0]):
        m = fn.lower(*a, **kw).compile().memory_analysis()
        print(json.dumps({
            "cell": cell.name, "program": name,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "generated_code_bytes": m.generated_code_size_in_bytes}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
