import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
# init. The placeholder devices exist ONLY in this process, ONLY for the
# dry-run; tests and benchmarks see the real single device.

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from repro import configs
from repro.launch import analysis, sharding as shd
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.specs import SHAPES, cell_supported, make_cell
from repro.models.partition import partitioning

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell and each production mesh
(single-pod 16x16 = 256 chips; multi-pod 2x16x16 = 512 chips):

    with mesh:
        lowered = jax.jit(step, in_shardings=..., out_shardings=...) \
            .lower(*input_specs(arch, shape))
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

Failures (sharding mismatch, OOM at compile, unsupported collective) are
bugs in the system. Results stream to a JSONL file consumed by
EXPERIMENTS.md §Dry-run and benchmarks/roofline.py.

Also runs the COBS index cells: the sharded signature-index query step
lowered on the same meshes (documents over ("pod","data"), Bloom rows over
"model") — the paper's workload on the production topology.
"""


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             smoke: bool = False) -> dict:
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": mesh.devices.size}
    cfg = configs.get(arch, smoke=smoke)
    ok, why = cell_supported(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    t0 = time.time()
    try:
        cell = make_cell(arch, shape_name, mesh, smoke=smoke)
        with mesh, partitioning(mesh, shd.act_rules_for(mesh)):
            jitted = jax.jit(cell.step_fn,
                             in_shardings=cell.in_shardings,
                             out_shardings=cell.out_shardings,
                             donate_argnums=cell.donate_argnums)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = analysis.memory_analysis_dict(compiled)
        roof = analysis.analyze(compiled, cell.cfg, cell.shape,
                                chips=mesh.devices.size)
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1), memory=mem,
                   roofline=roof.as_dict(),
                   params=cell.cfg.param_count(),
                   active_params=cell.cfg.active_param_count())
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def run_cobs_cell(mesh, mesh_name: str, n_docs: int = 102_400,
                  n_terms_avg: int = 3_400_000, batch_queries: int = 64,
                  ell: int = 1024, score_method: str = "vertical",
                  score_dtype=None) -> dict:
    """Lower the sharded COBS query step at paper scale (100k documents,
    3.4M avg 31-mers) without allocating the index: the arena is a
    ShapeDtypeStruct, documents shard over ("pod","data"), rows over
    "model"."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import theory
    from repro.core.index import BitSlicedIndex, IndexParams
    from repro.index.distributed import DistributedIndex

    rec = {"arch": "cobs-index", "shape": f"query_b{batch_queries}",
           "mesh": mesh_name, "chips": mesh.devices.size}
    t0 = time.time()
    try:
        block_docs = 1024
        n_blocks = n_docs // block_docs
        w = theory.bloom_size(n_terms_avg, 0.3, 1)
        w = (w + 511) // 512 * 512
        # abstract index: arena rows = n_blocks * w (uniform-avg staircase)
        idx = BitSlicedIndex(
            arena=jax.ShapeDtypeStruct((n_blocks * w, block_docs // 32),
                                       jnp.uint32),
            row_offset=jnp.arange(n_blocks, dtype=jnp.int32) * w,
            block_width=jnp.full((n_blocks,), w, jnp.int32),
            doc_slot=jnp.arange(0, dtype=jnp.int32),      # unused in lowering
            doc_n_terms=jnp.arange(0, dtype=jnp.int32),
            block_docs=block_docs, n_docs=n_docs,
            params=IndexParams(),
        )
        # build the sharded engine WITHOUT device_put (abstract arena)
        dist = DistributedIndex.__new__(DistributedIndex)
        dist.mesh = mesh
        dist.doc_axes = tuple(a for a in ("pod", "data")
                              if a in mesh.axis_names)
        dist.row_axis = "model"
        dist.params = idx.params
        dist.score_method = score_method
        dist.score_dtype = score_dtype or jnp.int32
        dist.n_docs = n_docs
        import math as _m
        n_doc_shards = _m.prod(mesh.shape[a] for a in dist.doc_axes)
        n_row_shards = mesh.shape["model"]
        rows_padded = (idx.arena.shape[0] + n_row_shards - 1) \
            // n_row_shards * n_row_shards
        words_padded = (idx.arena.shape[1] + n_doc_shards - 1) \
            // n_doc_shards * n_doc_shards
        dist.doc_words = words_padded
        dist.total_rows = rows_padded
        dist.row_stripe = rows_padded // n_row_shards
        dist.words_local = words_padded // n_doc_shards
        dist.n_blocks = n_blocks
        dist.slots_per_block = words_padded * 32
        dist._score_jit = None
        dist._topk_jit = {}

        from jax.sharding import NamedSharding, PartitionSpec as P
        doc = dist.doc_axes if len(dist.doc_axes) > 1 else dist.doc_axes[0]
        arena_sds = jax.ShapeDtypeStruct((rows_padded, words_padded),
                                         jnp.uint32)
        body = dist._shard_body(topk=32)
        in_specs, out_specs = dist._specs(32)
        from jax import shard_map
        fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        terms = jax.ShapeDtypeStruct((batch_queries, ell, 2), jnp.uint32)
        nval = jax.ShapeDtypeStruct((batch_queries,), jnp.int32)
        with mesh:
            jitted = jax.jit(
                fn,
                in_shardings=(NamedSharding(mesh, P("model", doc)),
                              NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                              NamedSharding(mesh, P()), NamedSharding(mesh, P())))
            lowered = jitted.lower(
                arena_sds,
                jax.ShapeDtypeStruct((n_blocks,), jnp.int32),
                jax.ShapeDtypeStruct((n_blocks,), jnp.int32),
                terms, nval)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = analysis.memory_analysis_dict(compiled)
        cost = {}
        try:
            cost = compiled.cost_analysis() or {}
            if isinstance(cost, list):
                cost = cost[0]
        except Exception:
            pass
        coll = analysis.collective_bytes(compiled.as_text())
        index_bytes = rows_padded * words_padded * 4
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1), memory=mem,
                   index_bytes_total=index_bytes,
                   index_bytes_per_chip=index_bytes // mesh.devices.size,
                   flops_per_chip=float(cost.get("flops", 0.0)),
                   bytes_per_chip=float(cost.get("bytes accessed", 0.0)),
                   coll_breakdown=coll,
                   coll_bytes_per_chip=float(sum(coll.values())))
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all' or 'cobs'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs/shapes (CI)")
    ap.add_argument("--out", default=None, help="JSONL output path")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single-pod-16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi-pod-2x16x16",
                       make_production_mesh(multi_pod=True)))

    archs = configs.list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    out_path = Path(args.out) if args.out else None
    if out_path:
        out_path.parent.mkdir(parents=True, exist_ok=True)

    failures = 0
    records = []
    for mesh_name, mesh in meshes:
        if args.arch in ("all", "cobs"):
            rec = run_cobs_cell(mesh, mesh_name)
            records.append(rec)
            _emit(rec, out_path)
            failures += rec["status"] == "error"
        if args.arch == "cobs":
            continue
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mesh, mesh_name,
                               smoke=args.smoke)
                records.append(rec)
                _emit(rec, out_path)
                failures += rec["status"] == "error"

    ok = sum(r["status"] == "ok" for r in records)
    sk = sum(r["status"] == "skipped" for r in records)
    print(f"\n== dry-run done: {ok} ok, {sk} skipped, {failures} errors ==")
    return 1 if failures else 0


def _emit(rec: dict, out_path: Path | None) -> None:
    status = rec["status"]
    extra = ""
    if status == "ok" and "roofline" in rec:
        r = rec["roofline"]
        extra = (f" t_comp={r['t_compute_s']:.3e}s t_mem={r['t_memory_s']:.3e}s"
                 f" t_coll={r['t_collective_s']:.3e}s -> {r['bottleneck']}")
    elif status == "ok":
        extra = f" index/chip={rec.get('index_bytes_per_chip', 0)/2**30:.2f}GiB"
    elif status == "error":
        extra = " " + rec.get("error", "")
    elif status == "skipped":
        extra = " " + rec.get("reason", "")
    print(f"[{rec['mesh']}] {rec['arch']} x {rec['shape']}: {status}{extra}",
          flush=True)
    if out_path:
        with out_path.open("a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    sys.exit(main())
