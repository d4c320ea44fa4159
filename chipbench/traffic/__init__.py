"""Traffic generators, one module per kind: ``make(seed, params, vocab_size)
-> (x, y)`` int32 arrays for ``Trainer.fit(x=, y=)``. A traffic *mix* is a
JSON file of parameters here that names its generator under ``kind``."""
