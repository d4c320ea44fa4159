"""The table of a cell's cross-chip sums, from the chip's own compiler.

    PYTHONPATH=. python build/reduction_table.py                 # the dp4 cell
    PYTHONPATH=. python build/reduction_table.py --dump /root/scratch/dp4.hlo
    PYTHONPATH=. python build/reduction_table.py --text /root/scratch/dp4.hlo

A helper, run by no cell and no test: nothing runs on a device, so nothing
here is a time. It builds the cell's trainer as `chipbench/run.py` does, on
the devices of a DESCRIBED v5e:2x2 (`horovod_tpu/testing/described_v5e.py`),
lowers and compiles the step `Trainer.fit` runs (≈ 2 min for the dp4 cell,
a 5 MB text; ``--dump`` keeps it, ``--text`` reads one back in a second)
and prints what `horovod_tpu.analysis.hlo_audit.reduction_schedule` makes
of it: a row a cross-chip collective (sum, leaf scope, bytes, start / host
/ done instructions), then the bytes by scope and the hosts by what they
compute. It is the table `horovod_tpu.trace.step_reductions()` hands the
benchmark's readers (`chipbench/reduction_spans.py`) after a traced run
and `HVT_PROFILE` writes beside a dump. A PR on the four-chip step starts
here (PERF.md §3, §5; ROADMAP S10).
"""

from __future__ import annotations

import argparse
import os
import sys


def compiled_text(cell_name: str) -> str:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import run
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.testing import described_v5e

    # The program asks the attached devices (CPUs here) whether to
    # interpret its kernels: steered here, never by an option of its own.
    fa.default_interpret = lambda: False
    cell = run.load_cell(run.ROOT, cell_name)
    with described_v5e.described() as topo:
        trainer = run.build_trainer(cell, topo.devices[:cell["chips"]], 0)
        traffic = cell["traffic"]
        return trainer._train_step_donated.lower(*described_v5e.step_args(
            trainer, traffic["seq_len"], traffic["global_batch"],
        )).compile().as_text()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", default="cerebras-gpt-1.3b.seq2k.dp4")
    parser.add_argument("--text", help="read a compiled text, compile nothing")
    parser.add_argument("--dump", help="write the compiled text here")
    args = parser.parse_args()
    from chipbench.reduction_spans import general
    from horovod_tpu.analysis import hlo_audit

    if args.text:
        with open(args.text) as f:
            text = f.read()
    else:
        text = compiled_text(args.cell)
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    rows = hlo_audit.reduction_schedule(text)
    for r in rows:
        how = "asynchronous" if r.asynchronous else "synchronous"
        shape = ",".join(map(str, r.shape))
        print(f"{r.kind} {r.dtype}[{shape}] {r.nbytes / 1e6:.3f} MB {how} "
              f"channel {r.channel}  {r.scope}")
        print(f"    start {r.start}  done {r.done}")
        for host in r.hosts:
            print(f"    host {host.name}  {host.host_scope}")
    total = sum(r.nbytes for r in rows)
    share = hlo_audit.asynchronous_share(rows)
    print(f"\n{len(rows)} collectives, {total / 1e6:.3f} MB a step, "
          f"asynchronous share of the sums {share}")
    print("\nbytes by scope (MB, collectives):")
    by_scope: dict = {}
    for r in rows:
        mb, n = by_scope.get(general(r.scope), (0.0, 0))
        by_scope[general(r.scope)] = (mb + r.nbytes / 1e6, n + 1)
    for scope, (mb, n) in sorted(by_scope.items(), key=lambda kv: -kv[1][0]):
        print(f"  {mb:10.3f}  {n:3d}  {scope}")
    print("\nhosts by what they compute (reduction_hosts without the times):")
    hosts: dict = {}
    for r in rows:
        for host in r.hosts:
            key = general(host.host_scope)
            hosts[key] = hosts.get(key, 0) + 1
    for scope, n in sorted(hosts.items(), key=lambda kv: -kv[1]):
        print(f"  {n:3d}  {scope}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
