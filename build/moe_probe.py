"""The routed layer's parts alone on the chip, at the four routed cells'
shapes (tokens, width, router's experts, experts a token, experts held):

    chiprun -- env PYTHONPATH=. python build/moe_probe.py            # times
    chiprun -- env PYTHONPATH=. python build/moe_probe.py chosen <cell> <seed> ...
    ... python3 -m chipbench.run --workload <cell> ... --trace 1 && \
        PYTHONPATH=. python build/moe_probe.py families <cell>

**Times** (a JSON line a cell, milliseconds a call on the device, from a
profile of 12 calls): (a) `moe._route` as the package makes it (selection by counting) and
the sort form it replaced (one `jnp.sort` down the tokens + `jax.lax.top_k`,
kept here and in `tests/test_moe.py`), forward and forward + backward, and
whether the two choose the same experts; the two selections without the
router's matmul; (b) the dispatch's row gather, (c) the combine's gated
float32 scatter-add, (d) the transpose of each, every one a program of its
own on the indices a real routing of random tokens gives; (e) what took the
place of the scatter-adds (c) and (d): the rows' token order
(`segment_sum.token_order`, made once a layer) and the segment sum
`hvt_moe_combine` in its combine form (gated, float32 sum rounded once to
bfloat16) and its transpose form (weight 1), beside the cheapest other form:
the rows gathered into token order, then XLA's scatter-add told that its
indices are sorted.

**chosen**: for each cell named, its model as the benchmark builds it, the
parameters of its first step (the trainer's seed) and its first sequence:
one forward pass with the package's selection and one with the sort form
patched in; for every routed layer whether each program chose what sorting
its own logits selects (numpy, to the index and in order), and how far the
two programs' logits and choices lie apart.

**families**: after a traced run of the cell in this checkout (its profile
under `.chipbench_out/`), one JSON line: every op family of the steady steps
with events and ms a step (the result line prints the ten largest only: the
`sort` family falls off it), and the routed layer's by sub-scope (`route`,
`dispatch`, `experts`, `combine`, `shared`), forward and backward apart.

A part alone is no substitute for the cell: `PERF.md` takes its end-to-end
numbers from `python3 -m chipbench.run` only."""
import contextlib
import json
import math
import pathlib
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reduce
from horovod_tpu.models import moe
from horovod_tpu.ops import grouped_matmul as gmm_ops
from horovod_tpu.ops import segment_sum as seg_ops

ROOT = pathlib.Path(".").resolve()
SHAPES = {  # tokens, d_model, routed, k, held
    "solar-open2-250b.seq8k.1chip": (8192, 4096, 320, 8, 8),
    "kanana-2-30b-a3b.seq8k.1chip": (8192, 2048, 128, 6, 16),
    "granite-4.0-h-small.seq4k.1chip": (4096, 4096, 72, 10, 8),
    "laguna-xs.2.seq8k.1chip": (8192, 2048, 256, 8, 32),
}


def sorted_bias(logits, k):
    t, e = logits.shape
    return -jnp.sort(logits, axis=0)[t - min(t, max(1, round(t * k / e)))]


def sorted_largest(values, k):
    return jax.lax.top_k(values, k)[1].astype(jnp.int32)


@contextlib.contextmanager
def sort_form():
    """`moe`'s two selecting functions as the parent had them, while open."""
    kept = moe.level_bias, moe._largest
    moe.level_bias, moe._largest = sorted_bias, sorted_largest
    try:
        yield
    finally:
        moe.level_bias, moe._largest = kept


def timed_ms(fn, args, calls=12):
    """Milliseconds a call ON THE DEVICE: the program's own events in a
    profile of ``calls`` calls, the first and last dropped (as the benchmark
    reads a step: `chipbench/reduce.py`). The host's clock cannot tell these
    parts apart: it issues a call every ≈ 0.2 ms, and half of them are
    shorter."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as root:
        with jax.profiler.trace(root):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = max(pathlib.Path(root).glob("plugins/profile/*/*.xplane.pb"))
        chips = reduce.chips_from_rows(reduce.rows_from_xplane(str(path)))
    if not chips:  # no device plane: not on the chip
        return None
    steps = chips[0].steps
    return sum(dur for _, dur in steps) / len(steps) / 1e6


def times(name, shape):
    t, d, routed, k, held = shape
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    tokens = jax.random.normal(keys[0], (1, t, d)).astype(jnp.bfloat16)
    router = jax.random.normal(keys[1], (d, routed)) * d ** -0.5
    weight = jax.random.normal(keys[2], (1, t, k))
    logits = jnp.dot(tokens[0].astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    row = {"cell": name, "device": jax.devices()[0].device_kind,
           "shape": list(shape)}
    chosen = {}
    for form, patched in (("count", contextlib.nullcontext()),
                          ("sort", sort_form())):
        # Functions made anew a form: jit keeps its programs by function.
        def route(tokens, router):
            return moe._route(tokens, router, k=k, scale=2.5)

        def both(tokens, router):
            def loss(tokens, router):
                chosen, gates = route(tokens, router)
                return jnp.sum(gates * weight), chosen
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
                tokens, router)

        parts = {
            "bias": lambda x: moe.level_bias(x, k),
            "largest": lambda x: moe._largest(x, k),
            "select": lambda x: moe._largest(x + moe.level_bias(x, k), k),
        }
        with patched:
            chosen[form] = jax.jit(route)(tokens, router)[0]
            row[f"route_{form}_fwd_ms"] = timed_ms(
                jax.jit(route), (tokens, router))
            row[f"route_{form}_fwd_bwd_ms"] = timed_ms(
                jax.jit(both), (tokens, router))
            for part, fn in parts.items():
                row[f"{part}_{form}_ms"] = timed_ms(jax.jit(fn), (logits,))
    row["chosen_equal"] = bool(jnp.array_equal(chosen["count"], chosen["sort"]))

    # The dispatch's indices, as `moe._held_experts` makes them.
    n = t  # one sequence a step
    budget = gmm_ops.row_budget(min(n * k, math.ceil(
        moe.BUDGET_FACTOR * n * k * held / routed)))
    local = chosen["count"].reshape(-1)
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)[:budget]
    token_of = order // k
    rows = tokens[0]
    out = jax.random.normal(keys[3], (budget, d)).astype(jnp.bfloat16)
    gate = jnp.abs(weight).reshape(-1)[order]
    row["budget_rows"] = budget

    def gather(rows):
        return rows[token_of]

    def scatter_add(out, gate):
        return jnp.zeros((n, d), jnp.float32).at[token_of].add(
            out.astype(jnp.float32) * gate[:, None]).astype(jnp.bfloat16)

    def transpose_of(fn, *args):
        primal, vjp = jax.vjp(fn, *args)
        return jax.jit(vjp), (jnp.ones_like(primal),)

    row["argsort_ms"] = timed_ms(
        jax.jit(lambda keys: jnp.argsort(keys, stable=True)[:budget]), (local,))
    row["gather_ms"] = timed_ms(jax.jit(gather), (rows,))
    row["scatter_add_ms"] = timed_ms(jax.jit(scatter_add), (out, gate))
    row["gather_transpose_ms"] = timed_ms(*transpose_of(gather, rows))
    row["scatter_add_transpose_ms"] = timed_ms(
        *transpose_of(scatter_add, out, gate))

    # (e) The segment sum. Rows past the groups' total belong to no token
    # (in the layer the grouped matmul makes them zeros).
    total = jnp.minimum(jnp.sum(local < held), budget)
    held_rows = jnp.where((jnp.arange(budget) < total)[:, None], out, 0)
    by_token = jax.jit(seg_ops.token_order, static_argnums=(2, 3))(
        order, total, n, k)
    row["held_rows"] = int(total)

    def in_token_order(out, weight, dtype):
        """The cheapest other form: a gather into token order, then the
        scatter-add with sorted indices."""
        rows = out[by_token.by_token].astype(jnp.float32)
        if weight is not None:
            rows = rows * weight[by_token.by_token][:, None]
        return jnp.zeros((n + 1, d), jnp.float32).at[by_token.token].add(
            rows, indices_are_sorted=True)[:n].astype(dtype)

    row["token_order_ms"] = timed_ms(
        jax.jit(seg_ops.token_order, static_argnums=(2, 3)),
        (order, total, n, k))
    row["segment_sum_ms"] = timed_ms(jax.jit(
        lambda out, gate: seg_ops.segment_sum(
            out, gate, by_token, n, jnp.bfloat16)), (held_rows, gate))
    row["segment_sum_transpose_ms"] = timed_ms(jax.jit(
        lambda out: seg_ops.segment_sum(out, None, by_token, n, jnp.bfloat16)),
        (held_rows,))
    row["sorted_scatter_add_ms"] = timed_ms(jax.jit(
        lambda out, gate: in_token_order(out, gate, jnp.bfloat16)),
        (held_rows, gate))
    row["sorted_scatter_add_transpose_ms"] = timed_ms(jax.jit(
        lambda out: in_token_order(out, None, jnp.bfloat16)), (held_rows,))
    want = scatter_add(held_rows, gate).astype(jnp.float32)
    got = seg_ops.segment_sum(held_rows, gate, by_token, n, jnp.bfloat16)
    row["segment_sum_max_abs_diff"] = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    print(json.dumps(row), flush=True)


def selected_by_sorting(logits, k):
    """numpy's side: the selection of logits ``[B, T, E]`` as the sort form
    defines it (a stable sort: a tie to the lower index)."""
    b, t, e = logits.shape
    above = min(t, max(1, round(t * k / e)))
    bias = -np.sort(logits, axis=1)[:, t - above]
    values = logits + bias[:, None, :]
    return np.argsort(-values, axis=-1, kind="stable")[..., :k]


def first_step_chosen(name, seed):
    """Every routed layer's logits and `chosen` in one forward pass of the
    cell's model at its first step's parameters, with the package's
    selection and with the sort form patched in: whether each program's
    `chosen` is what sorting ITS OWN logits selects (numpy), and how far
    the two programs' logits and choices lie apart. (They are two compiled
    programs: where XLA fuses the cast of the layer's input into the
    router's matmul in one and not in the other, the logits differ in
    bfloat16's last place and with them some choices, whatever selects.)"""
    import horovod_tpu as hvt
    from chipbench import run

    cell = run.load_cell(ROOT, name)
    hvt.runtime.use_compilation_cache()
    hvt.init()
    traffic = cell["traffic"]
    trainer = run.build_trainer(cell, jax.devices()[:1], seed)
    zeros = jnp.zeros((1, traffic["seq_len"]), jnp.int32)
    params = jax.jit(lambda key: trainer.module.init(
        {"params": jax.random.split(key, 3)[0], "dropout": key}, zeros,
        train=False, labels=zeros)["params"])(jax.random.PRNGKey(seed))
    x, y = run.load_attr(
        ROOT / "chipbench" / "traffic" / f"{traffic['kind']}.py", "make")(
            seed, traffic, cell["family"].sizes(cell["config"])["vocab_size"])
    seen = []
    gates = moe._gates

    def noting(logits, chosen, **kwargs):  # `_route` hands it both
        jax.debug.callback(
            lambda l, c: seen.append((np.asarray(l), np.asarray(c))),
            logits, chosen)
        return gates(logits, chosen, **kwargs)

    by_form = {}
    moe._gates = noting
    try:
        for form, patched in (("count", contextlib.nullcontext()),
                              ("sort", sort_form())):
            seen.clear()

            def forward(params, xb, yb):  # anew a form: jit keeps by function
                return trainer.module.apply(
                    {"params": params}, xb, train=False, labels=yb)[0]

            with patched:
                loss = jax.jit(forward)(params, x[:1], y[:1])
            jax.block_until_ready(loss)
            jax.effects_barrier()
            by_form[form] = list(seen)
    finally:
        moe._gates = gates
    layers = []
    for logits, chosen in by_form["count"]:
        # Debug callbacks come in no promised order: a layer's other side is
        # the one whose logits lie nearest.
        other_logits, other_chosen = min(
            by_form["sort"], key=lambda other: np.abs(other[0] - logits).max())
        k = chosen.shape[-1]
        layers.append({
            "count_is_its_logits_sorted": bool(np.array_equal(
                chosen, selected_by_sorting(logits, k))),
            "sort_is_its_logits_sorted": bool(np.array_equal(
                other_chosen, selected_by_sorting(other_logits, k))),
            "logits_equal_share": float(np.mean(logits == other_logits)),
            "logits_max_abs_diff": float(np.abs(logits - other_logits).max()),
            "chosen_differing": int((chosen != other_chosen).sum()),
        })
    print(json.dumps({
        "cell": name, "seed": seed, "device": jax.devices()[0].device_kind,
        "routed_layers": len(layers),
        "chosen_shape": list(by_form["count"][0][1].shape),
        "layers": layers}), flush=True)


def families(name, least_ms=0.02):
    from chipbench import spans

    path, = (ROOT / ".chipbench_out" / name / "profile").glob(
        "plugins/profile/*/*.xplane.pb")
    scopes = spans.read(path)["scopes"]
    chip = reduce.chips_from_rows(reduce.rows_from_xplane(str(path)))[0]
    by_family, by_part = {}, {}
    for op, _, dur in chip.ops:
        family, scope = reduce.op_family(op), scopes.get(op, "")
        keys = [(by_family, family)]
        if moe.SCOPE in scope:
            part = scope.split(moe.SCOPE)[1].strip("/").split("/")[0]
            keys.append((by_part, (
                part, "bwd" if "transpose(" in scope else "fwd", family)))
        for table, key in keys:
            events, ns = table.get(key, (0, 0.0))
            table[key] = (events + 1, ns + dur)

    def rows(table):
        steps = len(chip.steps)
        return [[key, events / steps, round(ns / 1e6 / steps, 4)]
                for key, (events, ns) in sorted(
                    table.items(), key=lambda kv: -kv[1][1])
                if ns / 1e6 / steps >= least_ms]

    print(json.dumps({"cell": name, "steps": len(chip.steps),
                      "families": rows(by_family), "moe": rows(by_part)}))


if __name__ == "__main__":
    if sys.argv[1:2] == ["families"]:
        families(sys.argv[2])
    elif sys.argv[1:2] == ["chosen"]:
        for cell, seed in zip(sys.argv[2::2], sys.argv[3::2]):
            first_step_chosen(cell, int(seed))
    else:
        for cell in sys.argv[1:] or list(SHAPES):  # or t,d,routed,k,held
            times(cell, SHAPES.get(cell) or tuple(map(int, cell.split(","))))
