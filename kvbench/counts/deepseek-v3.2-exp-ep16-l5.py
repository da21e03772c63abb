"""What ``deepseek-v3.2-exp-ep16-l5`` needs, from shapes alone: latent
attention with q-LoRA, a lightning indexer that keeps ``index_topk`` keys a
query, one dense layer and routed layers of which this chip holds
``cfg.experts_held`` experts. What the algorithm needs and no more, so a
share of a peak computed from this can only be understated.

``prefill_flops``: per token the matmuls (attention's and the indexer's
projections, the dense or shared feed-forward, the router at its whole
width, and the routed experts a token is sent to HERE: ``k * held /
experts`` on average, 0.5 of an expert at 8 x 16 / 256; the truth of a run
is the ``assignments_held`` counter, which ``held_assignment_share`` reads);
per pair of query and key the indexer's ``2 * heads * width`` over every
key the query may see, and attention in its textbook form (a head's query
is nope + rope wide, its value ``head_dim``) over the ``min(keys,
index_topk)`` it keeps. The served prefill masks a dense absorbed attention
instead, which does more work a pair and over all keys.

``decode_attention_bytes``: what the kernels a trace calls
``pallas_paged_decode_attention`` must read. They are handed the sum of the
rows' keys, not the rows: a row of ``n`` keys reads ``min(n, index_topk)``
latents, which is at least ``n * index_topk / LONGEST_ROW`` for every ``n``
up to the longest row the deployment admits. That floor is what is
counted, so ``attn_decode_roofline`` reads low here by up to the ratio of a
row's keys to ``index_topk``; ``dsa_attend_roofline`` divides the true
count (the ``selected_keys`` counter) by the same kernels' time.
"""

from kvbench.trace.opcount import head_flops, keys_attended

# max_pages_per_seq x page_size of the configuration's engines: no row
# holds more keys.
LONGEST_ROW = 528 * 64


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    r, dr, heads = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.num_heads
    qr = cfg.q_lora_rank
    attn = (h * qr + qr * heads * (hd + dr)     # w_dq, wq
            + h * (r + dr)                      # w_dkv, w_kr
            + 2 * heads * r * hd                # w_uk, w_uv
            + heads * hd * h)                   # wo
    index = (qr * cfg.index_n_heads * cfg.index_head_dim
             + h * cfg.index_head_dim + h * cfg.index_n_heads)
    routed = len(cfg.moe_layers)
    inter = cfg.moe_intermediate_size
    sent_here = (cfg.num_experts_per_token * cfg.num_experts_held
                 / max(cfg.num_experts, 1))
    expert_layer = (h * cfg.num_experts
                    + 3 * h * inter * (max(cfg.n_shared_experts, 1)
                                       + sent_here))
    return 2.0 * (cfg.num_layers * (attn + index)
                  + (cfg.num_layers - routed) * 3 * h * cfg.intermediate_size
                  + routed * expert_layer)


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    index_pair = 2.0 * cfg.index_n_heads * cfg.index_head_dim
    attend_pair = 2.0 * cfg.num_heads * (2 * cfg.head_dim
                                         + cfg.qk_rope_head_dim)
    return (n * flops_per_token(cfg)
            # A query keeps min(its keys, index_topk): a window's count.
            + cfg.num_layers * (index_pair * keys_attended(pos, n)
                                + attend_pair * keys_attended(
                                    pos, n, cfg.index_topk or None))
            + head_flops(cfg))


def latent_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of ``keys`` cached latents (rank + rope + pad lanes), over all
    layers."""
    return float(cfg.num_layers * cfg.kv_cache_head_dim * kv_itemsize * keys)


def index_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of ``keys`` cached index keys, over all layers."""
    return float(cfg.num_layers * cfg.index_head_dim * kv_itemsize * keys)


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """A floor under the latent bytes one decode step's attention kernels
    read for rows that hold ``keys`` keys in all (see the module's text)."""
    share = min(1.0, cfg.index_topk / LONGEST_ROW) if cfg.index_topk else 1.0
    return latent_bytes(cfg, keys, kv_itemsize) * share
