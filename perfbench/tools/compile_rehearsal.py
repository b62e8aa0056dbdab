"""Compile-only rehearsal, here on the CPU and for no chip time: lower each
cell's own programs (the train superstep; the serve prefill and decode) for
a described ``v5e:2x2`` at the real sizes with the chip's own compiler and
print ``memory_analysis()`` per device, so that batch, remat and pool are
settled before the first chip call. Nothing runs: what this prints is
"compiled, not run" and never a time.

    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_rehearsal.py [--workload W]

It steers the program from outside, as a scratch script may: the engines'
``OnMesh.__call__`` is made to hand back the lowering instead of
dispatching, ``jax.default_backend`` reads "tpu" (so the flash path is
taken), and ``device_put`` lets shapes through.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax.sharding import Mesh, NamedSharding            # noqa: E402
from jax.sharding import PartitionSpec as P             # noqa: E402

from perfbench import run as run_mod                    # noqa: E402
from perfbench.lib import manifest as manifest_lib      # noqa: E402


class Lowered(Exception):
    def __init__(self, lowered):
        self.lowered = lowered


def steer():
    from tpudist import engine as E
    jax.config.update("jax_enable_compilation_cache", False)

    def call(self, *args):
        raise Lowered(self.lower(*args))
    E.OnMesh.__call__ = call
    jax.default_backend = lambda: "tpu"
    put = jax.device_put

    def device_put(x, *a, **kw):
        leaves = jax.tree.leaves(x)
        if leaves and all(isinstance(v, jax.ShapeDtypeStruct) for v in leaves):
            return x
        return put(x, *a, **kw)
    jax.device_put = device_put


def mesh_for(topo, chips: int):
    """The program's six-axis mesh over the described devices, as its
    default ``ParallelConfig`` lays them out: every chip on ``data``."""
    names = ("data", "pipe", "fsdp", "expert", "tensor", "context")
    devs = np.array(topo.devices[:chips]).reshape([chips, 1, 1, 1, 1, 1])
    return Mesh(devs, names)


def report(name, lowered):
    c = lowered.compile()
    m = c.memory_analysis()
    tot = (m.argument_size_in_bytes + m.output_size_in_bytes
           + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = c.as_text()
    print(f"{name}: compiled, not run: per device arguments "
          f"{m.argument_size_in_bytes:,} B, outputs "
          f"{m.output_size_in_bytes:,} B (aliased {m.alias_size_in_bytes:,}"
          f" B), temp {m.temp_size_in_bytes:,} B, code "
          f"{m.generated_code_size_in_bytes:,} B; live at once "
          f"{tot:,} B of 16,909,336,064 B; "
          f"{text.count('tpu_custom_call')} mosaic call site(s)", flush=True)


def sds(tree, shardings):
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                          sharding=s),
                        tree, shardings)


def train_cell(ctx, topo):
    from tpudist import engine as E
    from perfbench.lib import train_entry as te
    cfg = te.build_config(ctx, ctx.traffic["log_every"], epochs=2)
    mesh = mesh_for(topo, ctx.chips)
    from tpudist.config import resolve_steps_per_dispatch
    k = resolve_steps_per_dispatch(cfg)
    shape = jax.eval_shape(lambda: E.init_state(jax.random.PRNGKey(0), cfg))
    state = sds(shape, E.state_shardings(cfg, mesh))
    rep = NamedSharding(mesh, P())
    slab = (jax.ShapeDtypeStruct(
        (k, cfg.batch_size, cfg.model.max_seq_len + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(None, ("data", "fsdp")))),)
    total = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    step = E.make_superstep(cfg, mesh, k)
    try:
        step(state, total, slab, 0, k)
    except Lowered as e:
        report(f"{ctx.cell['name']} superstep k={k} remat={cfg.remat}",
               e.lowered)


def serve_cell(ctx, topo):
    from tpudist.models import get_model
    from tpudist.parallel import sharding as shd
    from tpudist.serve.engine import PagedServeEngine
    from perfbench.lib import serve_entry as se
    e = ctx.traffic["engine"]
    mc = se.model_config(ctx)
    mesh = mesh_for(topo, ctx.chips)
    model = get_model(mc.name)
    pshape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), mc))
    pspecs = shd.sanitize_specs(pshape, model.param_specs(mc), mesh)
    params = sds(pshape, shd.named(mesh, pspecs))
    eng = PagedServeEngine(
        mc, mesh, slots=e["slots"], max_seq=e["max_seq"],
        prompt_pad=e["prompt_pad"], decode_k=e["decode_k"],
        page_tokens=e["page_tokens"], pages=e["pages"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[e["dtype"]])
    rep = NamedSharding(mesh, P())
    from tpudist.serve.engine import PagedServeState
    spec, s = eng.spec, eng.slots
    pool = jax.ShapeDtypeStruct(spec.pool_shape, eng.dtype, sharding=rep)
    vec = lambda dt: jax.ShapeDtypeStruct((s,), dt, sharding=rep)
    state = PagedServeState(pool, pool, vec(jnp.int32), vec(jnp.int32),
                            vec(jnp.bool_), vec(jnp.int32))
    eng._note_program = lambda *a, **kw: None
    row = np.full((spec.max_pages_per_slot,), -1, np.int32)
    try:
        eng.prefill(params, state, np.zeros((1, e["prompt_pad"]), np.int32),
                    1, 0, 2, page_row=row)
    except Lowered as ex:
        report(f"{ctx.cell['name']} prefill pad={e['prompt_pad']}",
               ex.lowered)
    try:
        eng.decode(params, state, e["decode_k"])
    except Lowered as ex:
        report(f"{ctx.cell['name']} decode k={e['decode_k']}", ex.lowered)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append")
    a = ap.parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    manifest = manifest_lib.load()
    steer()
    for w in manifest["workloads"]:
        if a.workload and w["name"] not in a.workload:
            continue
        ns = argparse.Namespace(workload=w["name"], seed=1, seconds=10,
                                trace=0)
        ctx = run_mod.Ctx(ns, manifest)
        ctx.workdir = "/nonexistent"
        (train_cell if ctx.traffic["entry"] == "train" else serve_cell)(
            ctx, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
