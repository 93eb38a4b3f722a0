"""Static schedule of the MSDA gather kernel's row loop on a TPU v5e.

    PYTHONPATH=src python tools/kernel_schedule.py [--levels 5] [--save]

Compiles ``msda_gather`` at the paper geometry (the first ``--levels``
levels of the 1024x1024 pyramid, 8 heads of 32, 4 points, block_q 240)
ahead of time for a described v5e on this host, with the TPU compiler's
LLO dump on, and reads the final VLIW bundles of the kernel.  It prints,
per query step (one iteration of the kernel's query loop, inner loops
counted as often as they run): the bundles, the bundles per gathered
row, the SMEM accesses and the spill loads and stores among them, the
scalar ops, and how full the SMEM slot and the two scalar slots are.
A bundle is one cycle of the core, so the bundles per row predict the
kernel's device time.

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
HEADS, HEAD_DIM, POINTS, BLOCK_Q = 8, 32, 4, 240
DELAY_SLOTS = 4  # bundles that run after a taken branch
SCALAR_OPS = ("sld", "sst", "sadd", "ssub", "smul", "sshll", "sshra", "sand",
              "sor", "scalar_lea", "smov", "scmp", "scalar_select")


def compile_gather(levels: int, save: bool) -> None:
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
    shapes = PAPER_LEVELS[:levels]
    offs, rows = ops.pyramid_row_offsets(shapes)
    geoms = tuple((o, w + 2, ops.slab_rows((h, w)), False)
                  for o, (h, w) in zip(offs, shapes))
    G = msda_fwd.head_group(HEADS, HEAD_DIM)
    NG, L = HEADS // G, len(shapes)
    nq = -(-87296 // BLOCK_Q)
    n = G * BLOCK_Q * L * POINTS

    def arg(size, dtype, *lead):
        return jax.ShapeDtypeStruct((*lead, size), dtype, sharding=dev)

    args = (arg(NG * nq * msda_fwd.table_block(n), jnp.int32),
            arg(NG * nq * msda_fwd.table_block(4 * n), jnp.float32),
            arg(G * HEAD_DIM, jnp.float32, 1, NG, rows))
    jax.jit(lambda idx, w, slab: msda_fwd.msda_gather(
        slab, idx, w, levels=geoms, num_points=POINTS, head_dim=HEAD_DIM,
        block_q=BLOCK_Q, save_dtype=jnp.bfloat16 if save else None,
        interpret=False, vmem_limit=default_vmem_budget("TPU v5 lite"),
    )).lower(*args).compile()


def read_bundles(path: str):
    """{address: bundle text} and the addresses marked as loop bodies."""
    bundles, heads = {}, []
    for line in open(path):
        m = re.match(r"\s+(0x[0-9a-f]+|\d+)\s+(LB:)?.*?\{(.*)\}", line)
        if m:
            bundles[int(m.group(1), 0)] = m.group(3)
            if m.group(2):
                heads.append(int(m.group(1), 0))
    return bundles, heads


def loops_of(bundles, heads):
    """(start, end, trips) of every loop, outermost first.  A loop starts
    at the phi of the counter its exit test reads (the listing numbers
    branch targets apart from its addresses) and ends with the delay
    slots of its back branch."""
    text = "\n".join(bundles.values())
    pred = r" = scmp\.\w+\.s32\.totalorder (?:\(%p\w+\), )?(%s\w+)(?:, (\d+))?"
    out = []
    for addr, b in bundles.items():
        m = re.search(r"sbr\.rel \(!?(%p\w+)\) target bundleno = (\d+)", b)
        if not m or int(m.group(2)) >= addr:
            continue
        start = max(x for x in heads if x <= int(m.group(2)))
        test = re.search(re.escape(m.group(1)) + pred, text)
        trips = int(test.group(2)) if test and test.group(2) else None
        if test:
            inc = re.search(re.escape(test.group(1)) + r" = sadd\.s32 "
                            r"(?:\(%p\w+\), )?1, (%s\w+)", text)
            phis = [x for x, bb in bundles.items() if inc and re.search(
                re.escape(inc.group(1)) + " = sphi", bb)]
            start = min(phis, default=start)
        out.append((start, addr + DELAY_SLOTS, trips))
    return sorted(out, key=lambda t: t[0] - t[1])


def account(path: str, rows: int) -> str:
    bundles, heads = read_bundles(path)
    loops = loops_of(bundles, heads)
    q0, q1, _ = loops[1]  # loops[0] is the grid
    inner = [lp for lp in loops[2:] if q0 <= lp[0] and lp[1] <= q1]
    # the query loop's exit predicate guards the grid loop's own restores
    exit_pred = next(re.search(r"sbr\.rel \(!(%p\w+)\)", bundles[a]).group(1)
                     for a in range(q1, q0 - 1, -1)
                     if "sbr.rel (!" in bundles.get(a, ""))
    c = collections.Counter()
    for a in range(q0, q1 + 1):
        w = 1
        for s, e, n in inner:
            w *= n if s <= a <= e else 1
        c["bundles"] += w
        smem = scalar = 0
        for inst in bundles.get(a, "").split(";;"):
            m = re.search(r"=\s*([a-z_]+)", inst)
            op = m.group(1) if m else ""
            if f"({exit_pred})" in inst:
                continue
            scalar += op in SCALAR_OPS
            if op in ("sld", "sst") and "smem:" in inst:
                smem = 1
                c["smem"] += w
                c["spill_" + op] += w * ("_spill" in inst)
            if op in ("sadd", "scalar_lea", "sld", "vld", "vrot"):
                c[op] += w
        c["scalar"] += scalar * w
        c["smem_bundles"] += smem * w
    n = c["bundles"]
    return "\n".join([
        f"per query step of {rows} rows: {n} bundles, {n / rows:.3f} per row",
        f"SMEM accesses {c['smem']} ({c['smem'] / rows:.3f} per row): "
        f"spill loads {c['spill_sld']}, spill stores {c['spill_sst']}",
        f"scalar ops {c['scalar']}: sld {c['sld']}, sadd {c['sadd']}, "
        f"scalar_lea {c['scalar_lea']}; vector vld {c['vld']}, "
        f"vrot {c['vrot']}",
        f"slots: SMEM {c['smem_bundles'] / n:.1%} of bundles, "
        f"scalar {c['scalar'] / (2 * n):.1%} of 2 per bundle"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--levels", type=int, default=len(PAPER_LEVELS))
    ap.add_argument("--save", action="store_true",
                    help="the training forward (saved corners)")
    ap.add_argument("--dump-dir", default=None,
                    help="keep the LLO dump here (default: a temp dir)")
    ap.add_argument("--compile", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.compile:
        compile_gather(a.levels, a.save)
        return 0
    dump = a.dump_dir or tempfile.mkdtemp(prefix="llo-")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               TPU_WORKER_HOSTNAMES="localhost",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                "--xla_jf_dump_llo_text=true")
    cmd = [sys.executable, __file__, "--compile", "--levels", str(a.levels)]
    subprocess.run(cmd + (["--save"] if a.save else []), env=env,
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    found = [p for p in glob.glob(os.path.join(dump, "*msda_gather*"))
             if p.endswith("-final_bundles.txt")]
    if not found:
        print(f"no gather bundles under {dump}", file=sys.stderr)
        return 1
    from repro.kernels.msda_fwd import head_group

    rows = a.levels * head_group(HEADS, HEAD_DIM) * POINTS * 4
    print(account(found[0], rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
