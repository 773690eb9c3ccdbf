"""Operations and bytes that the Moonlight cell's decode steps need,
counted from shapes and the program's routing counters.

They count what the mathematics of the absorbed decode requires, never
what an implementation happens to do, so a change that removes waste
cannot push a share of the peak over 100 %:

* a decode token (a row of a request that still wants tokens): 2
  operations per weight of the projections it passes (MLA's q, latent,
  the absorbed W_uk and W_uv per head, o; the dense layer's MLP; each MoE
  layer's router and shared experts), the LoRA terms at their true rank,
  the latent attention at the filled length (each of the 16 heads scores
  the 576 values of every filled position and the new row, and sums the
  512 of the latent), and the LM head over the vocabulary slice;
* the routed experts: 2 operations per weight of each expert a (token,
  held expert) pair passes, over the pairs the program's counter saw,
  times the share of rows whose requests still want tokens;
* a step's bytes: every non-expert weight once, each held expert that
  received a pair once (the counter of experts used), the adapters of the
  batch's distinct tenants, and the latent rows of the filled positions
  read and the new ones written.

``m`` is a configuration file (its published keys).
"""

from __future__ import annotations

F32 = 4  # bytes per element of the float32 configuration


def _dims(m: dict):
    return (m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def layers(m: dict) -> tuple[int, int]:
    """(dense layers, MoE layers)."""
    dense = m["first_k_dense_replace"]
    return dense, m["num_hidden_layers"] - dense


def mla_weights(m: dict) -> int:
    """One layer's attention weights and its two norms' scales."""
    d, h, rank, nope, rope, vd = _dims(m)
    return (d * h * (nope + rope) + d * (rank + rope) + rank + rank * h * (nope + vd)
            + h * vd * d + 2 * d)


def expert_weights(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def moe_fixed_weights(m: dict) -> int:
    """One MoE layer's router, score bias and shared experts."""
    d, e = m["hidden_size"], m["router_experts"]
    return d * e + e + m["n_shared_experts"] * expert_weights(m)


def adapter_weights(m: dict) -> int:
    d, h, rank, nope, rope, vd = _dims(m)
    r = m["lora"]["rank"]
    per = {"q": r * (d + h * (nope + rope)), "v": r * (rank + h * vd)}
    return m["num_hidden_layers"] * sum(per[t] for t in m["lora"]["targets"])


def token_flops(m: dict, filled: int) -> int:
    """One decode token whose request holds ``filled`` cached positions,
    without the routed experts."""
    d, h, rank, nope, rope, vd = _dims(m)
    r = m["lora"]["rank"]
    dense, moe = layers(m)
    attn = 2 * (d * h * (nope + rope) + d * (rank + rope) + h * nope * rank + h * rank * vd
                + h * vd * d)
    attn += 2 * h * (rank + rope + rank) * (filled + 1)
    attn += 2 * r * (d + h * (nope + rope)) + 2 * h * r * (rank + vd)  # LoRA q and v
    mlp = 2 * 3 * d * m["intermediate_size"]
    moe_fixed = 2 * moe_fixed_weights(m)
    return (m["num_hidden_layers"] * attn + dense * mlp + moe * moe_fixed
            + 2 * d * m["vocab_size"])


def pair_flops(m: dict) -> int:
    """One (token, expert) pair through the expert."""
    return 2 * expert_weights(m)


def step_fixed_bytes(m: dict, rows: int) -> int:
    """Bytes of one step's non-expert weights: every layer's attention, the
    dense MLPs, the MoE layers' routers and shared experts, the LM head,
    the final norm and the rows' embeddings."""
    d = m["hidden_size"]
    dense, moe = layers(m)
    elems = (m["num_hidden_layers"] * mla_weights(m) + dense * 3 * d * m["intermediate_size"]
             + moe * moe_fixed_weights(m) + m["vocab_size"] * d + d + rows * d)
    return F32 * elems


def latent_bytes(m: dict, filled: list[int]) -> int:
    """The latent rows the live rows read (their filled positions) and
    write (the new one), in every layer."""
    width = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return F32 * m["num_hidden_layers"] * width * sum(p + 1 for p in filled)
