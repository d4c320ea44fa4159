"""The flash kernels' final VLIW schedules, from the chip's own compiler.

    PYTHONPATH=. python build/flash_bundles.py --cell kanana
    PYTHONPATH=. python build/flash_bundles.py --t 4096 --heads 24 --window 4096

A helper, run by no cell and no test: nothing runs on a device, so nothing
here is a time. It compiles ``jax.grad(flash_attention)`` for a DESCRIBED
v5e (as `tests/test_chip_compile.py` does) in a child process with

    LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"

which makes libtpu write, for each Mosaic kernel, its final schedule
(``*-71-final_bundles.txt``: one VLIW bundle a line, the predicated regions
marked) and the slots every bundle uses (``*-69-…-utilization.txt``; a
bundle has 4 MXU, 4 VALU, 3 load and ONE store slot). One bundle is one
cycle when nothing stalls. The child ABORTS once the kernels are written
(the dumper then looks for an HTML template this libtpu does not ship):
that is expected, and the parent reads what was dumped.

Per kernel it prints the grid loop cut at its branches — the straight-line
bundles every grid step pays (before / between / after), and each
predicated region (`pl.when`: the accumulator init, the update bodies, the
finalisation; the pipeline's own DMA branches are short and are folded into
the straight-line parts) — with the slot totals and the vector opcodes
that tell a mask being built: iota (`vlaneseq`), compares, selects.
A kernel PR starts from this table, not from a guess (PERF.md §3, §7).
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import shutil
import subprocess
import sys
import tempfile

# The three cells' calls (chipbench/configs): B, T, heads, Dk, Dv, window.
CELLS = {
    "kanana": dict(b=1, t=8192, heads=32, dk=192, dv=128, window=None),
    "starcoder2": dict(b=1, t=4096, heads=24, dk=128, dv=128, window=4096),
    "cerebras": dict(b=2, t=2048, heads=16, dk=128, dv=128, window=None),
}
# Every name a flash call can take; a backward is `hvt_flash_bwd` alone or
# `hvt_flash_dq` + `hvt_flash_dkv` (`flash_attention.fused_backward`).
KERNELS = ("hvt_flash_fwd", "hvt_flash_bwd", "hvt_flash_dq", "hvt_flash_dkv")
# A predicated region shorter than this is the pipeline's (a DMA issue, a
# semaphore wait), not a `pl.when` of the kernel.
MIN_REGION = 24
MASK_OPS = ("vlaneseq", "vcmp", "vsel")


def compile_in_child(args, dump_dir: str) -> None:
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
        LIBTPU_INIT_ARGS=(
            f"--xla_jf_dump_to={dump_dir} --xla_jf_dump_llo_text=true"),
    )
    child = [
        sys.executable, __file__, "--child", "--b", str(args.b),
        "--t", str(args.t), "--heads", str(args.heads),
        "--dk", str(args.dk), "--dv", str(args.dv),
    ]
    if args.window is not None:
        child += ["--window", str(args.window)]
    if args.block is not None:
        child += ["--block", str(args.block)]
    # Exit code ignored: the dumper aborts the process after the kernels.
    subprocess.run(child, env=env, stdout=subprocess.DEVNULL,
                   stderr=subprocess.DEVNULL, check=False)


def child_main(args) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import flash_attention as fa

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    qk = jax.ShapeDtypeStruct(
        (args.b, args.t, args.heads, args.dk), jnp.bfloat16,
        sharding=one_chip)
    v = jax.ShapeDtypeStruct(
        (args.b, args.t, args.heads, args.dv), jnp.bfloat16,
        sharding=one_chip)
    blocks = {} if args.block is None else dict(
        block_q=args.block, block_k=args.block)

    def loss(q, k, v):
        with jax.named_scope("attention"):
            out = fa.flash_attention(
                q, k, v, causal=True, window=args.window, interpret=False,
                **blocks)
        return out.astype(jnp.float32).sum()

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v).compile()


BUNDLE = re.compile(r"\s*(0x[0-9a-f]+|\d+)\s+(?:([A-Z]{2}):)?\s*>?\s*:?\s*>?\s*\{")
BRANCH = re.compile(r"sbr\.rel \((!?)%\w+\) target bundleno = \d+ \(0x[0-9a-f]+\), region = (\d+)")
OPCODE = re.compile(r"= (\w[\w.]*)")


def read_bundles(path):
    """[(label, text)] by bundle number."""
    out = []
    for line in open(path):
        m = BUNDLE.match(line)
        if m:
            assert int(m[1], 0) == len(out), (path, m[1], len(out))
            out.append((m[2], line))
    return out


def read_slots(path, n):
    head, _, body = open(path).read().partition("== UTILIZATION:\n")
    names = [s.strip() for s in head.splitlines()[1].split(",")]
    rows = [tuple(map(int, r.split())) for r in body.strip().splitlines()]
    if len(rows) != n:
        print(f"  (slot rows {len(rows)} != bundles {n}: slot totals are "
              "approximate)")
    return names, rows


def cut(bundles, first, last, depth=0):
    """[(depth, kind, first, last)] of bundles[first..last]: 'step' =
    straight-line bundles, 'when' = a predicated region of at least
    MIN_REGION bundles with none nested in it; a region that holds others
    (the pipeline's own "this step has work" wrapper) is cut in turn."""
    pieces, at, i = [], first, first
    while i <= last:
        m = BRANCH.search(bundles[i][1])
        if m and not m[1]:
            close = next(
                (j for j in range(i + 1, last + 1)
                 if bundles[j][0] == "PF" and re.search(
                     rf"region {m[2]}\b", bundles[j][1])), None)
            if close is not None and close - i - 1 >= MIN_REGION:
                inner = cut(bundles, i + 1, close - 1, depth + 1)
                if len(inner) == 1:
                    inner = [(depth + 1, "when", i + 1, close - 1)]
                pieces += [(depth, "step", at, i)] + inner
                at = i = close
                continue
        i += 1
    if at <= last:
        pieces.append((depth, "step", at, last))
    return pieces


def cut_loop(bundles):
    """The grid loop, from its header to its back branch (the last branch
    of the file)."""
    lb = next(i for i, (lab, _) in enumerate(bundles) if lab == "LB")
    end = max(i for i, (_, t) in enumerate(bundles) if BRANCH.search(t))
    return cut(bundles, lb, end - 1)


def report(name, bundle_path, slot_path):
    bundles = read_bundles(bundle_path)
    slot_names, slots = read_slots(slot_path, len(bundles))
    print(f"\n{name}: {len(bundles)} bundles")
    print(f"  {'piece':<12}{'bundles':>8}  " + " ".join(
        f"{s:>6}" for s in slot_names[:7]) + "   mask ops")
    for depth, kind, a, b in cut_loop(bundles):
        ops = collections.Counter()
        for _, text in bundles[a:b + 1]:
            ops.update(m[1] for m in OPCODE.finditer(text))
        mask = {k: sum(n for op, n in ops.items() if op.startswith(k))
                for k in MASK_OPS}
        total = [sum(r[c] for r in slots[a:b + 1]) for c in range(7)]
        print(f"  {'. ' * depth + kind:<12}{b - a + 1:>8}  " + " ".join(
            f"{t:>6}" for t in total) + "   " + " ".join(
            f"{k} {n}" for k, n in mask.items() if n))


def report_dump(dump: str) -> None:
    """Every kernel of KERNELS the dump holds a schedule of; one that is not
    there is said to be absent (the backward took the other form), and a
    dump with none at all is a compile that failed before the kernels."""
    schedules = {
        kernel: sorted(glob.glob(
            os.path.join(dump, f"*{kernel}*-71-final_bundles.txt")))
        for kernel in KERNELS}
    if not any(schedules.values()):
        print("\nno schedule dumped (the compile failed before the "
              "kernels: run the --child command by hand)")
        return
    for kernel, found in schedules.items():
        if not found:
            print(f"\n{kernel}: absent from this call")
        for path in found:
            stem = path[:-len("-71-final_bundles.txt")]
            slots = glob.glob(stem + "-69-*utilization.txt")[0]
            report(os.path.basename(stem).split("-", 1)[1], path, slots)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", choices=sorted(CELLS))
    ap.add_argument("--b", type=int, default=1)
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dk", type=int, default=128)
    ap.add_argument("--dv", type=int, default=None)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--block", type=int, default=None,
                    help="block_q = block_k (default: what pick_blocks gives)")
    ap.add_argument("--keep", help="keep the dump in this directory")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.cell:
        for key, value in CELLS[args.cell].items():
            setattr(args, key, value)
    if args.dv is None:
        args.dv = args.dk
    if args.child:
        return child_main(args)
    dump = args.keep or tempfile.mkdtemp(prefix="flash_bundles_")
    os.makedirs(dump, exist_ok=True)
    try:
        compile_in_child(args, dump)
        print(f"B {args.b} T {args.t} heads {args.heads} Dk {args.dk} "
              f"Dv {args.dv} window {args.window} block {args.block}")
        report_dump(dump)
    finally:
        if not args.keep:
            shutil.rmtree(dump, ignore_errors=True)


if __name__ == "__main__":
    main()
