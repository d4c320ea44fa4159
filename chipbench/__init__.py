"""chipbench — the repository's benchmark on the chip (see README.md here).

Everything the yardstick needs lives under this directory: traffic
generation, the reduction from profiler traces to metrics, the table of
peaks, each model family's FLOP and byte counts and plain float32 reference
(families/), and the comparison that decides ``correct``. From the program
it takes only the system under test (`horovod_tpu.Trainer` and the model a
family builds)."""
