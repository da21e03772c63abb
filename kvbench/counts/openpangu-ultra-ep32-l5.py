"""What ``openpangu-ultra-ep32-l5`` needs, from shapes alone: latent attention
with q-LoRA in every layer, one dense layer and routed layers of which this
chip holds ``cfg.experts_held`` experts, and behind them the prediction
module: ``W_eh``, one more expert block and a second pass of the head. What
the algorithm needs and no more, so a share of a peak computed from this can
only be understated.

``prefill_flops``: per token the matmuls (attention's projections, the dense
or shared feed-forward, the router at its whole width, and the routed experts
a token is sent to HERE: ``k * held / experts`` on average, 0.25 of an expert
at 8 x 8 / 256), the module's layer and ``W_eh`` included; per pair of query
and key attention in its textbook form (a head's query is nope + rope wide,
its value ``head_dim``) over the six layers that keep latents; the head once
for the model and once for the module (a chunk samples one position).

``decode_attention_bytes``: what the kernels a trace calls
``pallas_paged_decode_attention`` must read for rows that hold ``keys`` keys
in all: a row's latents once a layer, whatever the positions verified, in
the six layers that keep them.

``verify_attention_flops``: what those kernels must multiply: every one of
``positions`` query positions a row, ``num_heads`` heads each, scores a key
over the latent's rank + rope lanes and weighs its rank lanes (the absorbed
form is the algorithm here: keys and values are the latent itself). At 128
heads and 2 positions that is 435 FLOP a byte read, beyond the chip's ridge
of 240: ``mla_verify_roofline`` takes the larger of the two bounds.
"""

from kvbench.trace.opcount import head_flops, keys_attended


def _latent_layers(cfg) -> int:
    """The layers that keep latents: the main ones and the module's."""
    return cfg.num_layers + cfg.num_nextn_predict_layers


def flops_per_token(cfg) -> float:
    h, hd = cfg.hidden_size, cfg.head_dim
    r, dr, heads = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.num_heads
    qr = cfg.q_lora_rank
    attn = (h * qr + qr * heads * (hd + dr)     # w_dq, wq
            + h * (r + dr)                      # w_dkv, w_kr
            + 2 * heads * r * hd                # w_uk, w_uv
            + heads * hd * h)                   # wo
    modules = cfg.num_nextn_predict_layers
    routed = len(cfg.moe_layers) + modules      # the module's is an expert block
    inter = cfg.moe_intermediate_size
    sent_here = (cfg.num_experts_per_token * cfg.num_experts_held
                 / max(cfg.num_experts, 1))
    expert_layer = (h * cfg.num_experts
                    + 3 * h * inter * (max(cfg.n_shared_experts, 1)
                                       + sent_here))
    return 2.0 * (_latent_layers(cfg) * attn
                  + (cfg.num_layers - len(cfg.moe_layers))
                  * 3 * h * cfg.intermediate_size
                  + routed * expert_layer
                  + modules * 2 * h * h)        # w_eh


def prefill_flops(cfg, pos: int, n: int) -> float:
    """One prefill chunk of ``n`` real tokens after ``pos`` cached ones."""
    if n <= 0:
        return 0.0
    attend_pair = 2.0 * cfg.num_heads * (2 * cfg.head_dim
                                         + cfg.qk_rope_head_dim)
    return (n * flops_per_token(cfg)
            + _latent_layers(cfg) * attend_pair * keys_attended(pos, n)
            + (1 + cfg.num_nextn_predict_layers) * head_flops(cfg))


def decode_attention_bytes(cfg, keys: int, kv_itemsize: int = 2) -> float:
    """Bytes of ``keys`` cached latents (rank + rope + pad lanes), once a
    row a layer, in the layers that keep them."""
    return float(_latent_layers(cfg) * cfg.kv_cache_head_dim * kv_itemsize
                 * keys)


def verify_attention_flops(cfg, keys: int, positions: int = 2) -> float:
    """FLOPs of attending ``keys`` cached latents (the rows' keys in all)
    from ``positions`` query positions a row, over the same layers."""
    per_key = 2.0 * positions * cfg.num_heads * (
        2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    return _latent_layers(cfg) * per_key * keys
