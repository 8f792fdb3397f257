"""A digest of the lowered text (StableHLO, no source locations) of the
serve programs of the serving cells that share ``mixed_programs``,
``KVCache``, ``TransformerConfig`` and the engine, from the tree given:

    JAX_PLATFORMS=cpu python tools/serve_programs_digest.py <root>

Run it on a copy of the parent commit and on the change and ``diff`` the
two outputs: equal digests mean a change to the table of kinds left
those cells' programs as they were (how PR 47 held its four neighbours;
nothing is compiled, a minute a tree)."""
import hashlib
import json
import os
import sys

root = os.path.abspath(sys.argv[1])
os.chdir(root)
sys.path.insert(0, root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.generators import serve_common  # noqa: E402
from horovod_tpu.models import init_transformer  # noqa: E402
from horovod_tpu.serve import decode as decode_lib  # noqa: E402
from horovod_tpu.serve.kv_cache import init_kv_cache, ring_width  # noqa: E402

CELLS = [("mistral-7b-v0.3-16l", "batch-prefill"),
         ("trinity-large-ep8-5l", "mixed-backlog-decode"),
         ("ling-3.0-flash-ep4-7l", "reasoning-backlog-longtail"),
         ("kimi-k2.7-code-ep32-6l", "repo-questions-backlog"),
         # its chunk programs changed with PR 49, its decode did not
         ("jamba2-3b", "chat-backlog"),
         # its chunk program changed with PR 51 and again with PR 53
         # (hvd_sparse_scores: a chunk's block scores), its decode did not
         ("minicpm-sala-8l", "longdoc-backlog"),
         # the decode programs of the three configurations with a full
         # kind (this, trinity, jamba) changed with PR 55
         # (hvd_paged_decode: a step's full layers), their chunks did not;
         # this one's three programs changed with PR 57 (hvd_grouped_matmul:
         # the whole mixture's grouped products), no other cell's did
         ("lfm2-8b-a1b-14l", "assistant-backlog"),
         # the one caller of paged_decode_stats (an eva layer's step: the
         # kernel twice, over the window's rows and the summaries' pages)
         ("evabyte-6.5b-8l", "bytedoc-backlog"),
         # with trinity's and ling's, the programs that changed with
         # PR 61 (hvd_grouped_matmul under a chip's share of the experts:
         # moe._held_rows); Kimi's share keeps lax.ragged_dot by its shapes
         # (its decode and ling's changed again with PR 62:
         # hvd_state_step, a step's mamba2 and kda layers; no chunk
         # program and no other cell's decode did; its two chunk
         # programs, and no other program of any cell, changed with
         # PR 64: hvd_ssd_scan, a chunk's SSD under mamba2_chunk)
         ("nemotron-3-super-120b-ep4-11l", "agent-backlog"),
         # the first configuration with a ring of latents, grouped and
         # differential latent heads, an mHC stream and PolyNorm (PR 63):
         # a tree before it does not build the configuration and says so
         ("motif-3-beta-ep8-5l", "mixed-longtail-backlog")]


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def digests(config: str, traffic: str):
    try:
        cfg = harness.model_config(
            harness.load_json("configs", config + ".json"))
    except (TypeError, ValueError, FileNotFoundError) as e:
        yield f"{config}", f"not built by this tree ({type(e).__name__})"
        return
    scfg = serve_common.serve_config(
        harness.load_json("traffic", traffic + ".json"))
    bs = scfg.block_size
    width = -(-(-(-scfg.max_prompt // bs) * bs + scfg.max_new_tokens) // bs)
    ring = (ring_width(cfg.attn_window,
                       scfg.prefill_chunk or max(scfg.prefill_buckets), bs)
            if cfg.n_window_layers or cfg.n_layers_of("mla_sliding") else 0)
    params = jax.eval_shape(
        lambda: init_transformer(cfg, jax.random.PRNGKey(0)))
    kc, vc = jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
        cfg, scfg.max_batch * width + 1, bs, n_slots=scfg.max_batch,
        ring=ring)))
    prefill, resume, decode = decode_lib.make_serve_fns(
        cfg, None, block_size=bs, table_width=width, ring=ring)[:3]
    b, t = scfg.batch_buckets[-1], max(scfg.prefill_buckets)
    # a mixed configuration's address is (block table, slot)
    one = (i32(width), i32()) if cfg.mixed else i32(width)
    rows = (i32(b, width), i32(b)) if cfg.mixed else i32(b, width)
    for name, fn, args in (
            ("prefill", prefill, (i32(t), i32(), one)),
            ("prefill_resume", resume, (i32(t), i32(), i32(), one)),
            ("decode", decode, (i32(b), i32(b), rows))):
        text = fn.lower(params, kc, vc, *args).as_text()
        yield (f"{config}:{name}",
               hashlib.sha256(text.encode()).hexdigest()[:16])


print(json.dumps({name: digest for cell in CELLS
                  for name, digest in digests(*cell)}, indent=1))
