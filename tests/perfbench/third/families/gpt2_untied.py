"""A third family, added as a file: GPT-2 with an output head of its own
(``tie_word_embeddings`` false), which the program also runs. Its sizes
and counts are the ``gpt2`` family's but for the head; its forward pass
is written out, since the tied one ends in the embedding's table."""
import jax

from families import gpt2
from families.gpt2 import SPLIT, attn_flops_per_token_fwd, kv_bytes_per_token, matmul_params  # noqa: F401
from pb import reference as R


def dims(cfg):
    return dict(gpt2.dims(cfg), tied=False)


def param_shapes(dims, max_seq):
    return dict(gpt2.param_shapes(dims, max_seq), lm_head=((dims["vocab"], dims["d"]), "w"))


def total_params(dims):
    return gpt2.total_params(dims) + dims["vocab"] * dims["d"]


def logits(params, tokens, dims, lowp=False):
    eps = dims["norm_eps"]
    x = params["wte"].astype(R.F32)[tokens] + params["wpe"].astype(R.F32)[: tokens.shape[1]]

    def layer(x, lp):
        a = R.layernorm(x, lp["ln1_g"], lp["ln1_b"], eps)
        qkv = R.mm("bsd,dthk->bsthk", a, lp["wqkv"], lowp) + lp["bqkv"]
        o = R.attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], 0, lowp)
        x = x + R.mm("bshk,hkd->bsd", o, lp["wo"], lowp) + lp["bo"]
        m = R.layernorm(x, lp["ln2_g"], lp["ln2_b"], eps)
        h = jax.nn.gelu(R.mm("bsd,df->bsf", m, lp["wi"], lowp) + lp["bi"], approximate=True)
        return x + R.mm("bsf,fd->bsd", h, lp["wo2"], lowp) + lp["bo2"], None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = R.layernorm(x, params["lnf_g"], params["lnf_b"], eps)
    return R.mm("bsd,vd->bsv", x, params["lm_head"], lowp)
