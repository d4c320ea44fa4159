"""Generation serving bundles: export the compiled decode loop, reload it
anywhere, serve it over HTTP.

The reference's serving story is an export tail: rank 0 saves a SavedModel
with a named predict signature "so that it can be served"
(/root/reference/mnist_keras.py:116-140). `checkpoint.export_serving`
covers that contract for classifiers; this module extends the same role to
the flagship generation stack: the KV-cache prefill + `lax.scan` decode
loop of `models/decoding.make_generate_fn` — greedy or
temperature/top-k/top-p sampling, eos early-stop, ragged prompt lengths —
is serialized **as one StableHLO program** via `jax.export`, with the
weights in msgpack beside it and the byte-BPE tokenizer JSON riding along,
so a serving host needs jax + this module, no flax model code and no
training checkpoint.

Bundle layout (``export_dir/<YYYYmmdd-HHMMSS>/`` — the reference's
timestamped-directory convention):

* ``generate.stablehlo`` — the exported program
  ``(params, prompt [B, T0], rng, lengths [B]) -> tokens [B, new]``;
* ``weights.msgpack``    — the param pytree (msgpack-restorable without a
  template);
* ``generate.json``      — shapes, sampling knobs, eos/pad ids, vocab;
* ``tokenizer.json``     — optional `data.tokenizer.ByteBPETokenizer`.

Ragged prompts are first-class: the program is compiled for one
``[batch_size, prompt_len]`` shape, and per-request prompts of any length
≤ ``prompt_len`` are right-padded server-side with per-row true lengths
passed through — each row generates exactly as if alone at its own length
(models/decoding.py ragged contract), so clients never see the static
shape.

Serve with ``python -m horovod_tpu.launch.serve <bundle_dir>`` — the
server routes ``/v1/generate`` for these bundles (launch/serve.py).
"""

from __future__ import annotations

import json
import os
import shutil
import time

import jax
import numpy as np
from flax import serialization

GEN_GRAPH_FILE = "generate.stablehlo"
GEN_START_FILE = "generate_start.stablehlo"  # streaming bundles
GEN_CONT_FILE = "generate_cont.stablehlo"
GEN_META_FILE = "generate.json"
GEN_WEIGHTS_FILE = "weights.msgpack"
TOKENIZER_FILE = "tokenizer.json"


def export_generate(
    export_dir: str,
    model,
    params,
    *,
    batch_size: int,
    prompt_len: int,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_id: int | None = None,
    pad_id: int = 0,
    tokenizer=None,
    timestamp: str | None = None,
    int8_compute: bool = False,
    quantized_cache: bool = False,
    speculative_gamma: int = 0,
    streaming_chunk: int = 0,
) -> str:
    """Export a generation bundle into ``export_dir/<stamp>/``.

    ``model`` is the *training* `TransformerLM` (or any module
    `make_generate_fn` accepts); ``params`` its param pytree — plain,
    single-host sharded (TP/FSDP assemble transparently), or sharded
    across processes, in which case this is a COLLECTIVE: every process
    must call export_generate, the shards are host-gathered
    (`checkpoint.gather_to_host`), the primary writes the bundle and
    non-primaries return None. ``tokenizer`` is a `ByteBPETokenizer`, a
    path to a saved tokenizer JSON, or None (token-id-only serving).

    The exported program takes params as an ARGUMENT (not baked-in
    constants): the graph stays small, and the weights live once, in
    msgpack. Sampling knobs are compile-time (they shape the program);
    the rng seed and prompts are runtime inputs.
    """
    from horovod_tpu.models.decoding import make_generate_fn

    if prompt_len < 1 or batch_size < 1:
        raise ValueError(
            f"batch_size ({batch_size}) and prompt_len ({prompt_len}) "
            "must be >= 1"
        )
    from horovod_tpu import checkpoint as ckpt
    from horovod_tpu import runtime

    if ckpt.is_cross_process_sharded(params):
        params = ckpt.gather_to_host(params)  # collective — see docstring
        if not runtime.is_primary():
            return None
    # int8_compute / quantized_cache: the decode-family quantization knobs
    # (models/quant.py) baked into the exported program — int8-MXU prefill
    # and/or the int8 K/V cache, the serving levers.
    # speculative_gamma > 0: the bundle's program is the SPECULATIVE
    # decoder (models/speculative.py, prompt-lookup draft) — greedy-exact
    # output at 2.4-3.3x measured throughput; greedy-only and no eos (the
    # speculative loop's restrictions), ragged lengths supported the same.
    # All validation happens BEFORE the output dir exists, so a rejected
    # export never litters export_dir with an empty timestamped dir.
    if speculative_gamma:
        if temperature != 0.0:
            raise ValueError(
                "speculative bundles are greedy-only (temperature == 0): "
                "the exported program carries no rng input"
            )
        if eos_id is not None:
            raise ValueError(
                "speculative decoding does not support eos early-stop — "
                "export without eos_id or without speculative_gamma"
            )
        if int8_compute:
            raise ValueError(
                "int8_compute is not wired into the speculative loop — "
                "export with one or the other"
            )
        from horovod_tpu.models.speculative import make_speculative_fn

        fn = make_speculative_fn(
            model.clone(quantized_cache=True) if quantized_cache else model,
            max_new_tokens=max_new_tokens, gamma=speculative_gamma,
            include_prompt=False,
        )
    else:
        fn = make_generate_fn(
            model,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_id=eos_id,
            include_prompt=False,
            int8_compute=int8_compute,
            quantized_cache=quantized_cache,
        )
    # streaming_chunk > 0: the bundle carries TWO programs (prefill+first
    # chunk; continue-against-carried-cache) so a server can stream tokens
    # chunk by chunk — `make_chunked_generate_fns`, whose token stream is
    # parity-tested against the one-shot generator. Exclusive with the
    # speculative program (one program shape per bundle).
    start_fn = cont_fn = None
    if streaming_chunk:
        if speculative_gamma:
            raise ValueError(
                "streaming_chunk and speculative_gamma are exclusive — "
                "one program shape per bundle"
            )
        if int8_compute:
            raise ValueError(
                "int8_compute is not wired into the chunked generator — "
                "export with one or the other"
            )
        from horovod_tpu.models.decoding import make_chunked_generate_fns

        start_fn, cont_fn = make_chunked_generate_fns(
            model, max_new_tokens=max_new_tokens, chunk=streaming_chunk,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, quantized_cache=quantized_cache,
        )
    stamp = timestamp or time.strftime("%Y%m%d-%H%M%S")
    out_dir = os.path.join(export_dir, stamp)
    os.makedirs(out_dir, exist_ok=True)
    from jax import export as jax_export

    params = jax.device_get(params)
    param_specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        params,
    )
    prompt_spec = jax.ShapeDtypeStruct((batch_size, prompt_len), np.int32)
    rng_spec = (
        None if speculative_gamma else jax.ShapeDtypeStruct(
            np.shape(jax.random.PRNGKey(0)),
            np.asarray(jax.random.PRNGKey(0)).dtype,
        )
    )
    lengths_spec = jax.ShapeDtypeStruct((batch_size,), np.int32)
    from horovod_tpu.checkpoint import _atomic_write

    if streaming_chunk:
        exp_start = jax_export.export(start_fn)(
            param_specs, prompt_spec, rng_spec, lengths_spec
        )
        state_spec = jax.eval_shape(
            start_fn, param_specs, prompt_spec, rng_spec, lengths_spec
        )[1]
        exp_cont = jax_export.export(cont_fn)(param_specs, state_spec)
        _atomic_write(
            os.path.join(out_dir, GEN_START_FILE), exp_start.serialize()
        )
        _atomic_write(
            os.path.join(out_dir, GEN_CONT_FILE), exp_cont.serialize()
        )
    else:
        exported = jax_export.export(fn)(
            param_specs, prompt_spec, rng_spec, lengths_spec
        )
        _atomic_write(
            os.path.join(out_dir, GEN_GRAPH_FILE), exported.serialize()
        )
    _atomic_write(
        os.path.join(out_dir, GEN_WEIGHTS_FILE),
        serialization.to_bytes(params),
    )
    meta = {
        "kind": "generate",
        "batch_size": batch_size,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "temperature": temperature,
        "top_k": top_k,
        "top_p": top_p,
        "eos_id": eos_id,
        "pad_id": pad_id,
        "int8_compute": int8_compute,
        "quantized_cache": quantized_cache,
        "speculative_gamma": speculative_gamma,
        "streaming_chunk": streaming_chunk,
        "has_tokenizer": tokenizer is not None,
        "created": stamp,
    }
    # Tokenizer BEFORE the meta that advertises it: a crash between the two
    # writes leaves a bundle whose meta under-promises, never one that lies.
    if tokenizer is not None:
        tok_path = os.path.join(out_dir, TOKENIZER_FILE)
        if isinstance(tokenizer, str):
            shutil.copyfile(tokenizer, tok_path)
        else:
            tokenizer.save(tok_path)
    _atomic_write(
        os.path.join(out_dir, GEN_META_FILE),
        json.dumps(meta, indent=2).encode(),
    )
    return out_dir


def is_generate_bundle(bundle_dir: str) -> bool:
    return os.path.exists(os.path.join(bundle_dir, GEN_META_FILE))


class GenerateBundle:
    """A reloaded generation bundle: tokenize → pad → run → trim → detok.

    ``generate_tokens(prompts, seed)`` takes a list of token-id sequences
    (each of length 1..prompt_len); requests of any row count are split /
    padded to the compiled batch internally. ``generate_text(texts, seed)``
    adds the tokenizer round-trip (requires the bundle to carry one).
    Generations are trimmed at ``eos_id`` when the bundle was exported
    with one.
    """

    def __init__(self, bundle_dir: str):
        from jax import export as jax_export

        self.bundle_dir = bundle_dir
        with open(os.path.join(bundle_dir, GEN_META_FILE)) as f:
            self.meta = json.load(f)
        if self.meta.get("kind") != "generate":
            raise ValueError(f"{bundle_dir} is not a generation bundle")
        if self.meta.get("streaming_chunk"):
            with open(os.path.join(bundle_dir, GEN_START_FILE), "rb") as f:
                self._start = jax.jit(jax_export.deserialize(f.read()).call)
            with open(os.path.join(bundle_dir, GEN_CONT_FILE), "rb") as f:
                self._cont = jax.jit(jax_export.deserialize(f.read()).call)
            self._call = None
        else:
            with open(os.path.join(bundle_dir, GEN_GRAPH_FILE), "rb") as f:
                self._exported = jax_export.deserialize(f.read())
            # jit the deserialized program ONCE: a bare exported.call
            # re-lowers on every invocation (measured seconds per request
            # at LM scale); under jit the compilation caches and repeat
            # calls are a dispatch.
            self._call = jax.jit(self._exported.call)
        with open(os.path.join(bundle_dir, GEN_WEIGHTS_FILE), "rb") as f:
            self._params = serialization.msgpack_restore(f.read())
        # Commit the weights to device ONCE: params are an ARGUMENT of the
        # exported program, and host numpy args would re-transfer the whole
        # model through the interconnect on every request.
        import jax.numpy as jnp

        self._params = jax.tree.map(jnp.asarray, self._params)
        self.tokenizer = None
        tok_path = os.path.join(bundle_dir, TOKENIZER_FILE)
        if os.path.exists(tok_path):
            from horovod_tpu.data.tokenizer import ByteBPETokenizer

            self.tokenizer = ByteBPETokenizer.load(tok_path)
        elif self.meta.get("has_tokenizer"):
            # Fail fast on an inconsistent bundle (tokenizer.json lost in
            # transfer) instead of silently degrading to token-id-only
            # serving while /healthz advertises a tokenizer.
            raise FileNotFoundError(
                f"{bundle_dir} advertises a tokenizer "
                f"(generate.json has_tokenizer=true) but {TOKENIZER_FILE} "
                "is missing — the bundle is incomplete"
            )

    @property
    def batch_size(self) -> int:
        return int(self.meta["batch_size"])

    @property
    def prompt_len(self) -> int:
        return int(self.meta["prompt_len"])

    def stream_chunks(self, prompts, seed: int = 0, chunk: int = 0):
        """STREAMING generation: yields ``[B_req, chunk]``-shaped lists of
        token ids per dispatch (the cache stays device-resident between
        chunks). Requires a streaming bundle (``streaming_chunk`` at
        export) and at most ``batch_size`` validated prompts; stops early
        once every row has emitted eos (when configured). The
        concatenation of the yielded chunks equals the one-shot
        generation for the same knobs (parity-tested)."""
        k = int(self.meta.get("streaming_chunk") or 0)
        if not k:
            raise ValueError(
                "this bundle was not exported with streaming_chunk — "
                "re-export to stream"
            )
        prompts = self.validate_prompts(prompts)
        b, t0 = self.batch_size, self.prompt_len
        if not prompts or len(prompts) > b:
            raise ValueError(
                f"streaming takes 1..{b} prompts per request, got "
                f"{len(prompts)}"
            )
        n = len(prompts)
        pad = int(self.meta.get("pad_id") or 0)
        padded = np.full((b, t0), pad, np.int32)
        lengths = np.ones((b,), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = p
            lengths[i] = len(p)
        # Same per-group rng discipline as _run: group 0 uses PRNGKey(seed)
        # verbatim (local-parity contract), later groups of an
        # over-batch-size request fold the group index in.
        rng = jax.random.PRNGKey(seed)
        if chunk:
            rng = jax.random.fold_in(rng, chunk)
        tokens, state = self._start(self._params, padded, rng, lengths)
        yield np.asarray(tokens)[:n].tolist()
        total = int(self.meta["max_new_tokens"])
        for _ in range(total // k - 1):
            if self.meta.get("eos_id") is not None and bool(
                np.asarray(state[3])[:n].all()
            ):
                return  # every live row finished — stop dispatching
            tokens, state = self._cont(self._params, state)
            yield np.asarray(tokens)[:n].tolist()

    def _run(self, padded: np.ndarray, lengths: np.ndarray, seed: int,
             chunk: int = 0):
        if self.meta.get("streaming_chunk"):
            # Streaming bundles dispatch via stream_chunks (the one-shot
            # API collects in generate_batch's streaming branch).
            raise RuntimeError("_run is not used for streaming bundles")
        if self.meta.get("speculative_gamma"):
            # Speculative bundles are greedy: no rng input in the program
            # (the seed is ignored — deterministic by construction).
            return np.asarray(
                self._call(
                    self._params,
                    padded.astype(np.int32),
                    None,
                    lengths.astype(np.int32),
                )
            )
        # Chunk 0 uses PRNGKey(seed) verbatim — the documented parity
        # contract with a local `fn(params, prompt, PRNGKey(seed), lens)`
        # call. Later chunks of an over-batch-size request fold the chunk
        # index in so sampled generations don't repeat across chunks.
        rng = jax.random.PRNGKey(seed)
        if chunk:
            rng = jax.random.fold_in(rng, chunk)
        return np.asarray(
            self._call(
                self._params,
                padded.astype(np.int32),
                rng,
                lengths.astype(np.int32),
            )
        )

    def validate_prompts(self, prompts) -> list:
        """Normalize to int32 row arrays; guided error outside 1..T0."""
        t0 = self.prompt_len
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        for i, p in enumerate(prompts):
            if not 1 <= len(p) <= t0:
                raise ValueError(
                    f"prompt {i} has {len(p)} tokens; this bundle serves "
                    f"prompts of 1..{t0} tokens"
                )
        return prompts

    def generate_batch(self, prompts, seed: int = 0, chunk: int = 0) -> list:
        """ONE device call over ≤ batch_size validated prompt rows →
        trimmed generated-id lists. The unit the server's coalescing queue
        dispatches (launch/serve.py). (Streaming bundles run their chunk
        loop here — same token stream, more dispatches.)"""
        b, t0 = self.batch_size, self.prompt_len
        if len(prompts) > b:
            raise ValueError(
                f"{len(prompts)} rows > compiled batch {b}; use "
                "generate_tokens for auto-splitting"
            )
        if self.meta.get("streaming_chunk"):
            # One-shot API over a streaming bundle: collect the chunks
            # (same token stream — chunking is where dispatches cut, not
            # what is computed). The batch-group index threads through so
            # sampled over-batch-size requests don't repeat across groups.
            rows = [[] for _ in prompts]
            for chunk_tokens in self.stream_chunks(
                prompts, seed=seed, chunk=chunk
            ):
                for i, part in enumerate(chunk_tokens):
                    rows[i].extend(part)
            return [self._trim(np.asarray(r)) for r in rows]
        pad = int(self.meta.get("pad_id") or 0)
        n = len(prompts)
        padded = np.full((b, t0), pad, np.int32)
        lengths = np.ones((b,), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = p
            lengths[i] = len(p)
        gen = self._run(padded, lengths, seed, chunk=chunk)[:n]
        return [self._trim(row) for row in gen]

    def generate_tokens(self, prompts, seed: int = 0) -> list:
        """``prompts``: list of token-id sequences → list of generated-id
        lists (prompt not included; trimmed at eos when configured)."""
        b = self.batch_size
        prompts = self.validate_prompts(prompts)
        if not prompts:
            return []
        out: list = []
        for ci, start in enumerate(range(0, len(prompts), b)):
            out.extend(
                self.generate_batch(
                    prompts[start : start + b], seed=seed, chunk=ci
                )
            )
        return out

    def _trim(self, row: np.ndarray) -> list:
        eos = self.meta.get("eos_id")
        row = [int(t) for t in row]
        if eos is None:
            return row
        return row[: row.index(eos)] if eos in row else row

    def generate_text(self, texts, seed: int = 0) -> list:
        if self.tokenizer is None:
            raise ValueError(
                "this bundle has no tokenizer.json — export with "
                "tokenizer=... or POST token ids to /v1/generate instead"
            )
        prompts = [self.tokenizer.encode(t) for t in texts]
        for i, p in enumerate(prompts):
            if len(p) > self.prompt_len:
                raise ValueError(
                    f"text {i} tokenizes to {len(p)} tokens; this bundle "
                    f"serves prompts of up to {self.prompt_len} tokens"
                )
        gen = self.generate_tokens(prompts, seed=seed)
        return [self.tokenizer.decode(g) for g in gen]


def load_generate(bundle_dir: str) -> GenerateBundle:
    """Reload an `export_generate` bundle."""
    return GenerateBundle(bundle_dir)
