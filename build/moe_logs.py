"""`chiprun -- env PYTHONPATH=. python build/moe_logs.py <cell> <seed> <steps>`:
the cell's trainer for some steps with the step logs the benchmark does not
print (the routed layers' sown metrics), min / max over the steps."""
import json, pathlib, sys
import jax
import horovod_tpu as hvt
from chipbench import run
ROOT = pathlib.Path(".").resolve()
cell = run.load_cell(ROOT, sys.argv[1]); seed = int(sys.argv[2]); steps = int(sys.argv[3])
hvt.runtime.use_compilation_cache(); jax.config.update("jax_persistent_cache_min_compile_time_secs", 0); hvt.init()
traffic = cell["traffic"]
trainer = run.build_trainer(cell, jax.devices()[:1], seed); run.init_state(trainer, traffic["seq_len"])
x, y = run.load_attr(ROOT / "chipbench" / "traffic" / f"{traffic['kind']}.py", "make")(seed, traffic, cell["family"].sizes(cell["config"])["vocab_size"])
seen = []
class Logs(hvt.callbacks.Callback):
    def on_batch_end(self, batch, logs=None):
        seen.append(dict(logs))
trainer.fit(x=x, y=y, batch_size=1, cache=None, verbose=0, steps_per_epoch=steps, epochs=1, callbacks=[Logs()])
seen = [{k: float(v) for k, v in log.items()} for log in seen]
print(json.dumps({"cell": cell["name"], "seed": seed, "steps": len(seen), **{k: [min(s[k] for s in seen), max(s[k] for s in seen)] for k in seen[0]}, "loss_first_last": [seen[0]["loss"], seen[-1]["loss"]], "load_by_20": [seen[i]["moe_load_max_over_mean"] for i in range(0, len(seen), 20)]}))
