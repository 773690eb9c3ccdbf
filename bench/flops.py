"""Operations and bytes that the algorithm needs, counted from shapes.

These count what the mathematics requires and never what an implementation
happens to do, so a later change that removes waste cannot push a share of
the peak over 100 %:

* a forward token through a GPT-2 block stack: 2 operations per weight of
  the dense projections, the LoRA terms ``x·A`` and ``h·B`` at their true
  rank, and causal attention at the true lengths (query t reads t keys);
* a training token through a frozen backbone with LoRA: the forward, the
  activation gradients (each dense projection once more; attention twice,
  for both operands of its two products), and LoRA's weight gradients; no
  gradient of a frozen weight, no recomputation; layer 0's projections
  need no gradient of their input, since the embeddings are frozen;
* the LM head at the last position only, over the columns the step reads;
* a decode step's bytes: the weights once, the batch's adapters, and the
  keys and values of the filled positions only.

Models are the ``models`` entries of a configuration file (``d_model``,
``d_ff``, ``num_layers``, ``vocab_size``, ``lora.rank``, ``lora.targets``).
"""

from __future__ import annotations

F32 = 4  # bytes per element of the float32 configurations


def _sizes(m: dict):
    return (m["d_model"], m["d_ff"], m["num_layers"], m["lora"]["rank"],
            len(m["lora"]["targets"]))


def dense_token_flops(m: dict) -> int:
    """One token through one layer's projections: q, k, v, o, the MLP and
    the LoRA terms."""
    d, d_ff, _, r, n_lora = _sizes(m)
    return 2 * 4 * d * d + 2 * 2 * d * d_ff + n_lora * 2 * 2 * d * r


def attention_flops(m: dict, seq: int) -> int:
    """Causal attention of one sequence of ``seq`` tokens in one layer:
    query t scores and mixes t keys, 4·d·t operations."""
    d = m["d_model"]
    return 2 * d * seq * (seq + 1)


def forward_flops(m: dict, batch: int, seq: int, head_cols: int) -> int:
    """A forward pass of ``batch`` sequences, LM head at the last position
    over ``head_cols`` columns."""
    d, _, n_layers, _, _ = _sizes(m)
    per_layer = batch * (seq * dense_token_flops(m) + attention_flops(m, seq))
    return n_layers * per_layer + batch * 2 * d * head_cols


def train_flops(m: dict, batch: int, seq: int, head_cols: int) -> int:
    """Forward and backward of a LoRA-only update on ``batch`` sequences."""
    d, _, n_layers, r, n_lora = _sizes(m)
    tokens = batch * seq
    dense = n_layers * tokens * dense_token_flops(m)
    # layer 0: no gradient of the (frozen) embeddings through q, k, v or A
    unneeded = tokens * (3 * 2 * d * d + n_lora * 2 * d * r)
    attn = n_layers * batch * attention_flops(m, seq)
    lora_weight_grads = n_layers * tokens * n_lora * 2 * 2 * d * r
    head = batch * 2 * d * head_cols
    return (2 * dense - unneeded + 3 * attn + lora_weight_grads + 2 * head)


def fed_round_flops(client: dict, server: dict, *, cohort: int, public: int,
                    seq: int, batch: int, local_steps: int, distill_steps: int,
                    server_steps: int, num_classes: int, eval_size: int) -> int:
    """One steady federated round: each client distills, fine-tunes and
    infers the public set; the server distills and re-infers it; the server
    and one client are evaluated."""
    vocab = client["vocab_size"]
    per_client = (distill_steps * train_flops(client, public, seq, vocab)
                  + local_steps * train_flops(client, batch, seq, num_classes)
                  + forward_flops(client, public, seq, vocab))
    srv = (server_steps * train_flops(server, public, seq, vocab)
           + forward_flops(server, public, seq, vocab))
    evals = (forward_flops(server, eval_size, seq, num_classes)
             + forward_flops(client, eval_size, seq, num_classes))
    return cohort * per_client + srv + evals


def decode_step_flops(m: dict, filled: list[int]) -> int:
    """One decode step of a batch whose sequences hold ``filled[b]`` cached
    tokens before the new one."""
    d, _, n_layers, _, _ = _sizes(m)
    total = 0
    for p in filled:
        attn = 4 * d * (p + 1)
        total += n_layers * (dense_token_flops(m) + attn) + 2 * d * m["vocab_size"]
    return total


def weight_bytes(m: dict) -> int:
    """The frozen float32 weights one decode step reads: every layer, the
    tied embedding (it is the LM head too) and one position's row."""
    d, d_ff, n_layers, _, _ = _sizes(m)
    per_layer = 4 * d * d + 4 * d + 2 * d * d_ff + d_ff + d + 4 * d
    embeds = (m["vocab_size"] + 1) * d
    return F32 * (n_layers * per_layer + embeds + 2 * d)


def adapter_bytes(m: dict) -> int:
    d, _, n_layers, r, n_lora = _sizes(m)
    return F32 * n_layers * n_lora * 2 * d * r


def decode_step_bytes(m: dict, filled: list[int], adapters: int) -> int:
    """Least bytes one decode step moves: the weights once, ``adapters``
    distinct adapters, the keys and values of the filled positions read,
    and the new token's written."""
    d, _, n_layers, _, _ = _sizes(m)
    kv = sum(p + 2 for p in filled) * n_layers * 2 * d * F32
    return weight_bytes(m) + adapters * adapter_bytes(m) + kv
