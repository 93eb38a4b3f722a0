"""Static schedule of the MSDA gather kernel on a TPU v5e.

    PYTHONPATH=src python tools/kernel_schedule.py [--sca] [--levels k]
        [--block-q n] [--save]

Compiles ``msda_gather`` ahead of time for a described v5e on this host,
with the TPU compiler's LLO dump on, and reads the kernel's final VLIW
bundles and the compiler's count of what each bundle issues on each
unit.  Two geometries, 8 heads of 32 in both: DETR's encoder at the
paper's input (the first ``--levels`` levels of the 1024x1024 pyramid,
87,296 queries, 4 points), or with ``--sca`` BEVFormer's spatial
cross-attention over its largest camera (4 levels 116x200..15x25, 9,664
queries, 8 points); the query block is the one the planner commits on a
v5e unless ``--block-q`` names one.  It prints, for one grid step (a
query block, every loop in it counted as often as it runs, so the
per-block work shows), per query and per gathered row: the bundles, the
SMEM accesses and the spill loads and stores among them, the scalar ops,
the vector ALU, cross-lane (XLU) and MXU ops, the MXU weight pushes, and
how full the SMEM slot, the two scalar slots, the four VALU slots, the
three XLU slots and the three vector-load slots are.  A bundle is one
cycle of the core, so the bundles per row predict the kernel's device
time.

The compile runs in a child process: the dumper aborts after writing
the bundle files (it misses a report template), and only one process
may hold the TPU library.  Nothing runs on a chip.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

PAPER_LEVELS = ((256, 256), (128, 128), (64, 64), (32, 32), (16, 16))
SCA_LEVELS = ((116, 200), (58, 100), (29, 50), (15, 25))
HEADS, HEAD_DIM = 8, 32
# (levels, points, queries) of a geometry: DETR's encoder at the paper's
# input, BEVFormer's spatial cross-attention over its largest camera
GEOMETRIES = {"encoder": (PAPER_LEVELS, 4, 87296), "sca": (SCA_LEVELS, 8, 9664)}
DELAY_SLOTS = 4  # bundles that run after a taken branch
SCALAR_OPS = ("sld", "sst", "sadd", "ssub", "smul", "sshll", "sshra", "sand",
              "sor", "scalar_lea", "smov", "scmp", "scalar_select")


def geometry(sca: bool, levels: int, block_q: int):
    """(level shapes, points, queries, block_q): the planner's committed
    whole-pyramid block for a v5e unless ``block_q`` is given."""
    from repro.kernels import msda_fwd, ops
    from repro.kernels.plan import default_vmem_budget

    shapes, points, queries = GEOMETRIES["sca" if sca else "encoder"]
    shapes = shapes[:levels] if levels else shapes
    if not block_q:
        block_q = ops.plan_blocks(
            shapes, points, HEAD_DIM, queries, train=False,
            vmem_budget=default_vmem_budget("TPU v5 lite"),
            heads=msda_fwd.head_group(HEADS, HEAD_DIM))
    return shapes, points, queries, block_q


def compile_gather(sca: bool, levels: int, block_q: int, save: bool) -> None:
    """AOT-compile the gather for a described v5e (child process)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import msda_fwd, ops
    from repro.kernels.plan import default_vmem_budget

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
        chips_per_host_bounds=(1, 1, 1), num_slices=1)
    dev = SingleDeviceSharding(topo.devices[0])
    shapes, points, queries, block_q = geometry(sca, levels, block_q)
    offs, rows = ops.pyramid_row_offsets(shapes)
    geoms = tuple((o, w + 2, ops.slab_rows((h, w)))
                  for o, (h, w) in zip(offs, shapes))
    G = msda_fwd.head_group(HEADS, HEAD_DIM)
    NG, L = HEADS // G, len(shapes)
    nq = -(-queries // block_q)
    R = msda_fwd.weight_layout(L, points, HEAD_DIM)[0]

    def arg(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=dev)

    args = (arg(jnp.int32,
                NG * nq * msda_fwd.table_block(G * block_q * L * points)),
            arg(jnp.float32, 1, NG, nq * block_q * R, G * HEAD_DIM),
            arg(jnp.float32, 1, NG, rows, G * HEAD_DIM))
    jax.jit(lambda idx, w, slab: msda_fwd.msda_gather(
        slab, idx, w, levels=geoms, num_points=points, head_dim=HEAD_DIM,
        block_q=block_q, save_dtype=jnp.bfloat16 if save else None,
        interpret=False, vmem_limit=default_vmem_budget("TPU v5 lite"),
    )).lower(*args).compile()


def read_bundles(path: str):
    """{address: bundle text} and the addresses that start a loop body."""
    bundles, heads = {}, []
    for line in open(path):
        m = re.match(r"\s+(0x[0-9a-f]+|\d+)\s+(LB:)?.*?\{(.*)\}", line)
        if m:
            bundles[int(m.group(1), 0)] = m.group(3)
            if m.group(2):
                heads.append(int(m.group(1), 0))
    return bundles, heads


def loops_of(bundles, heads):
    """(start, end, trips) of every loop, outermost first.  A loop ends
    with the delay slots of the branch its exit test guards; its trips
    are that test's bound.  Loops nest, so the branches close the loop
    bodies innermost first."""
    text = "\n".join(bundles.values())
    bounds = dict(re.findall(
        r"(%p\w+) = scmp\.\w+\.s32\.totalorder [^;]*?, (\d+) "
        r"/\* loop exit test", text))
    open_heads, out = sorted(heads), []
    for addr in sorted(bundles):
        m = re.search(r"sbr\.rel \(!(%p\w+)\)", bundles[addr])
        if m and m.group(1) in bounds:
            start = max(h for h in open_heads if h < addr)
            open_heads.remove(start)
            out.append((start, addr + DELAY_SLOTS, int(bounds[m.group(1)])))
    return sorted(out, key=lambda t: t[0] - t[1])


# the units of a v5e bundle, in the order the compiler's per-bundle
# utilization dump lists them, and how many of each one bundle holds
UNITS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
         "VSTORE:SPILL", "SALU")


def read_utilization(path: str):
    """Per bundle, in address order: the ops it issues on each unit."""
    rows = [line.split() for line in open(path)]
    return [list(map(int, r)) for r in rows
            if len(r) == len(UNITS) and all(x.isdigit() for x in r)]


def account(path: str, util_path: str, block_q: int, rows: int) -> str:
    """One grid step (a query block), every loop in it counted as often
    as it runs, per query and per gathered row."""
    bundles, heads = read_bundles(path)
    util = read_utilization(util_path)
    loops = loops_of(bundles, heads)
    g0, g1, _ = loops[0]  # the grid
    inner = [lp for lp in loops[1:] if g0 <= lp[0] and lp[1] <= g1]
    c = collections.Counter()
    for a in range(g0, g1 + 1):
        w = 1
        for s, e, n in inner:
            w *= n if s <= a <= e else 1
        c["bundles"] += w
        smem = scalar = 0
        for inst in bundles.get(a, "").split(";;"):
            m = re.search(r"=\s*([a-z_]+)", inst)
            op = m.group(1) if m else ""
            scalar += op in SCALAR_OPS
            if op in ("sld", "sst") and "smem:" in inst:
                smem = 1
                c["smem"] += w
                c["spill_" + op] += w * ("_spill" in inst)
            if op in ("sadd", "scalar_lea", "sld", "vmatpush"):
                c[op] += w
        c["scalar"] += scalar * w
        c["smem_bundles"] += smem * w
        for unit, used in zip(UNITS, util[a] if a < len(util) else ()):
            c[unit] += used * w
    n, per_row = c["bundles"], block_q * rows

    def r(key):
        return f"{c[key] / per_row:.3f}"

    return "\n".join([
        f"per grid step of {block_q} queries: {n} bundles, "
        f"{n / block_q:.1f} per query, {n / per_row:.3f} per row",
        f"SMEM accesses {r('smem')} per row: spill loads "
        f"{c['spill_sld']}, spill stores {c['spill_sst']} per step",
        f"scalar ops {r('scalar')} per row: sld {r('sld')}, sadd "
        f"{r('sadd')}, scalar_lea {r('scalar_lea')}",
        f"vector ops per row: VALU {r('VALU')}, XLU {r('XLU')}, MXU "
        f"{r('MXU')} (+ {r('vmatpush')} weight pushes), loads "
        f"{r('VLOAD')}, stores {r('VSTORE')}",
        f"slots: SMEM {c['smem_bundles'] / n:.1%} of bundles, scalar "
        f"{c['scalar'] / (2 * n):.1%} of 2, VALU {c['VALU'] / (4 * n):.1%} "
        f"of 4, XLU {c['XLU'] / (3 * n):.1%} of 3, vector loads "
        f"{c['VLOAD'] / (3 * n):.1%} of 3 per bundle"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sca", action="store_true",
                    help="BEVFormer's spatial cross-attention geometry")
    ap.add_argument("--levels", type=int, default=0,
                    help="the first k levels only (default: all)")
    ap.add_argument("--block-q", type=int, default=0,
                    help="query block (default: the planner's for a v5e)")
    ap.add_argument("--save", action="store_true",
                    help="the training forward (saved corners)")
    ap.add_argument("--dump-dir", default=None,
                    help="keep the LLO dump here (default: a temp dir)")
    ap.add_argument("--compile", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.compile:
        compile_gather(a.sca, a.levels, a.block_q, a.save)
        return 0
    dump = a.dump_dir or tempfile.mkdtemp(prefix="llo-")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               TPU_WORKER_HOSTNAMES="localhost",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                "--xla_jf_dump_llo_text=true")
    cmd = [sys.executable, __file__, "--compile", "--levels", str(a.levels),
           "--block-q", str(a.block_q)]
    cmd += ["--sca"] * a.sca + ["--save"] * a.save
    subprocess.run(cmd, env=env, stdout=subprocess.DEVNULL,
                   stderr=subprocess.DEVNULL)
    found = [p for p in glob.glob(os.path.join(dump, "*msda_gather*"))
             if p.endswith("-final_bundles.txt")]
    util = [p for p in glob.glob(os.path.join(dump, "*msda_gather*"))
            if p.endswith("-final_hlo-static-per-bundle-utilization.txt")]
    if not found or not util:
        print(f"no gather bundles under {dump}", file=sys.stderr)
        return 1
    from repro.kernels.msda_fwd import head_group

    shapes, points, _, block_q = geometry(a.sca, a.levels, a.block_q)
    rows = len(shapes) * head_group(HEADS, HEAD_DIM) * points * 4
    print(f"{'sca' if a.sca else 'encoder'}: {len(shapes)} levels, "
          f"{points} points, block_q {block_q}, {rows} rows per query")
    print(account(found[0], util[0], block_q, rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
