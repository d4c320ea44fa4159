"""`PYTHONPATH=. python build/cell_compile.py [cell] [--dump | --lower-only] [--lower-dump]`:
a benchmark cell's train step, as `chipbench.run` builds it, compiled for the
described v5e (no chip, nothing runs): arguments + temporaries and the Mosaic
calls by name; `--dump` keeps the compiled text under build/cell_step.txt;
`--lower-only` stops at the sha of the lowered text, to compare two trees'
programs, and `--lower-dump` keeps that text under build/lowered_<cell>.txt
(of the tree it is run from, as every path here). `LAYERS` / `KINDS` / `HEAD_CHUNKS` in the environment cut an
`ssm_moe_lm` cell's depth, kinds and head chunks, to see what a layer adds."""
import os, sys, json, pathlib, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
import horovod_tpu as hvt
from chipbench import run
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import mesh as mesh_lib
sys.path.insert(0, "tests")
import test_chip_compile as tcc

fa.default_interpret = lambda: False
ROOT = pathlib.Path(".").resolve()
cell = run.load_cell(ROOT, sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith("-") else "granite-4.0-h-small.seq4k.1chip")
if os.environ.get("LAYERS"):
    n = int(os.environ["LAYERS"])
    kinds = os.environ.get("KINDS", "mamba").split(",")
    cell["config"]["num_hidden_layers"] = n
    cell["config"]["layer_types"] = (kinds * n)[:n]
if os.environ.get("HEAD_CHUNKS"):
    cell["workload"]["trainer"]["fused_head_chunks"] = int(os.environ["HEAD_CHUNKS"])
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
t = time.time()
trainer = run.build_trainer(cell, topo.devices[:cell["chips"]], 7)
if cell["config"]["family"] != "dense_lm":  # what the routed layers sow
    trainer._metric_names = ("moe_held_rows_share", "moe_load_max_over_mean", "moe_overflow_rows")
traffic = cell["traffic"]
lowered = trainer._train_step.lower(*tcc.abstract_step_args(trainer, traffic["seq_len"], traffic["global_batch"]))
import hashlib
import re
# A Mosaic call's serialized payload holds source paths and line numbers
# (PERF.md, PR 37): left out, two trees' programs compare by what they compute.
text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', lowered.as_text())
print("lowered sha", hashlib.sha256(text.encode()).hexdigest()[:16], len(text))
if "--lower-dump" in sys.argv:
    (ROOT / "build" / f"lowered_{cell['name']}.txt").write_text(text)
if "--lower-only" in sys.argv:
    sys.exit(0)
compiled = lowered.compile()
m = compiled.memory_analysis()
print(f"compile {time.time()-t:.0f}s arguments {m.argument_size_in_bytes/1e9:.3f} temp {m.temp_size_in_bytes/1e9:.3f} total {(m.argument_size_in_bytes+m.temp_size_in_bytes)/1e9:.3f} GB (temp {m.temp_size_in_bytes:,} bytes); output {m.output_size_in_bytes/1e9:.3f} alias {m.alias_size_in_bytes/1e9:.3f}")
print(sorted(set(tcc.kernel_names(compiled))), len(tcc.kernel_names(compiled)))
import collections
print(collections.Counter(tcc.kernel_names(compiled)))
if "--dump" in sys.argv:
    pathlib.Path("build/cell_step.txt").write_text(compiled.as_text())
