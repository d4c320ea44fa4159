"""A family the harness has never seen, as a later PR would bring it: a small
flax LM the program does not have (RMSNorm, a gated SiLU MLP, heads of 24 on
a width of 64, an auxiliary loss and a metric sown in every layer), its plain
reference, its own counts and its own limits, all in one file. The tests
copy it to ``chipbench/families/gated_toy.py`` of a temporary copy of the
benchmark (test_chipbench.py `add_toy_configuration`); nothing imports it
from here but them."""

import flax.linen as nn
import jax
import jax.numpy as jnp


# What `chipbench.reference.compare`'s report is held to in a cell of this
# family (run.py `limits_of`; ``bias`` keeps run.py's 1e-3, since none is
# stated here): float32 against its float32 reference, so far tighter than
# the dense families' three constants, and on the two numbers that a
# minority of tokens cannot move. Set on the CPU at the tests' toy width
# (14 seeds, sequences of 64; PR 29):
LIMITS = {
    # sound: 4.77e-7 on every seed (one float32 step of a loss of 4.5; 0
    # where more than half of the tokens agree to the bit: 12-26 of 64 did);
    # every row of the reference's mask without its diagonal: 0.085-0.173
    "median_abs_diff": 2e-5,
    # sound: 0.0 on every seed; the mask's last eight rows of 64 without
    # their diagonal: 0.125 (the median stays 4.77e-7)
    "far_off_share": 0.05,
}
# A token is far off where its loss differs by more than this: sound, the
# furthest token of any seed is 2.4e-6 off; of the eight tokens that the
# last-eight-rows fault moves, the nearest is 6.9e-4 to 2.0e-2 off.
FAR_OFF = 1e-4


def sizes(config):
    return {"vocab_size": config["vocab_size"],
            "max_positions": config["max_position_embeddings"],
            "attention_layers": config["num_hidden_layers"]}


class GatedLM(nn.Module):
    vocab: int
    width: int
    heads: int
    head_dim: int
    layers: int
    mlp: int

    @nn.compact
    def __call__(self, tokens, train=False, labels=None):
        x = nn.Embed(self.vocab, self.width, name="embed")(tokens)
        t = tokens.shape[1]
        seen = jnp.tril(jnp.ones((t, t), bool))
        for n in range(self.layers):
            h = nn.RMSNorm(name=f"norm_attn_{n}")(x)
            q, k, v = (nn.DenseGeneral((self.heads, self.head_dim),
                                       use_bias=False, name=f"{w}_{n}")(h)
                       for w in "qkv")
            scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(
                float(self.head_dim))
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            out = jnp.einsum("bhts,bshd->bthd", probs, v)
            x = x + nn.DenseGeneral(self.width, axis=(-2, -1),
                                    use_bias=False, name=f"o_{n}")(out)
            h = nn.RMSNorm(name=f"norm_mlp_{n}")(x)
            gate = nn.Dense(self.mlp, use_bias=False, name=f"gate_{n}")(h)
            up = nn.Dense(self.mlp, use_bias=False, name=f"up_{n}")(h)
            x = x + nn.Dense(self.width, use_bias=False,
                             name=f"down_{n}")(nn.silu(gate) * up)
            self.sow("losses", f"gate_penalty_{n}", 1e-3 * jnp.mean(gate ** 2))
            self.sow("metrics", "gate_rms", jnp.sqrt(jnp.mean(gate ** 2)))
        logits = nn.Dense(self.vocab, use_bias=False, name="head")(
            nn.RMSNorm(name="norm_out")(x))
        if labels is None:
            return logits
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        loss = jax.nn.logsumexp(logits, -1) - picked
        return loss, (jnp.argmax(logits, -1) == labels).astype(jnp.float32)


def build(config, trainer_spec, mesh):
    return GatedLM(
        vocab=config["vocab_size"], width=config["hidden_size"],
        heads=config["num_attention_heads"], head_dim=config["head_dim"],
        layers=config["num_hidden_layers"], mlp=config["intermediate_size"])


def _rms_norm(x, scale):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * scale


def _seen(t):
    """[T, T]: query i sees the keys j <= i."""
    return jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]


def per_token_loss(params, tokens, labels, config):
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"]["embedding"][tokens]
        seen = _seen(tokens.shape[0])
        for n in range(config["num_hidden_layers"]):
            h = _rms_norm(x, p[f"norm_attn_{n}"]["scale"])
            q, k, v = (jnp.einsum("td,dhe->the", h, p[f"{w}_{n}"]["kernel"])
                       for w in "qkv")
            scores = jnp.einsum("the,she->hts", q, k) / jnp.sqrt(
                float(config["head_dim"]))
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            out = jnp.einsum("hts,she->the", probs, v)
            x = x + jnp.einsum("the,hed->td", out, p[f"o_{n}"]["kernel"])
            h = _rms_norm(x, p[f"norm_mlp_{n}"]["scale"])
            gate = h @ p[f"gate_{n}"]["kernel"]
            x = x + (jax.nn.silu(gate) * (h @ p[f"up_{n}"]["kernel"])
                     ) @ p[f"down_{n}"]["kernel"]
        logits = _rms_norm(x, p["norm_out"]["scale"]) @ p["head"]["kernel"]
        picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked


def required_flops_per_token(config, seq_len):
    d, width = config["hidden_size"], (
        config["num_attention_heads"] * config["head_dim"])
    layer = 4 * d * width + 3 * d * config["intermediate_size"]
    params = config["num_hidden_layers"] * layer + d * config["vocab_size"]
    pairs = seq_len * (seq_len + 1) // 2
    dots = 6 * 2 * pairs * width * config["num_hidden_layers"]
    return 6.0 * params + dots / seq_len


def kernel_work(config, seq_len, per_chip_batch):
    return {}  # dense attention: no kernel of its own
