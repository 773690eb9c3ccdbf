"""FL client: local LoRA fine-tuning + sparsified knowledge upload
(Algorithm 1, client loop: lines 3-12)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.channel import ChannelState, topk_budget
from repro.core.protocol import PayloadSpec, UplinkPayload, lora_projection_bits
from repro.core.topk import SparseLogits, topk_sparsify
from repro.data.pipeline import epoch_batches
from repro.data.synthetic import IntentDataset
from repro.fed import steps as fed_steps
from repro.models import init as model_init

__all__ = ["ClientUpload", "Client", "make_upload_payload"]


def make_upload_payload(
    cfg: ModelConfig,
    client_id: int,
    num_samples: int,
    k: int,
    *,
    send_h: bool,
    value_bits: int,
    snr_db: float,
    quantize: bool = False,
) -> tuple[UplinkPayload, int | None]:
    """The single source of truth for one upload's on-air accounting
    (shared by Client.upload and the batched engine, so ledger parity can't
    drift).  Returns (payload, lora_rank or None).

    ``quantize`` prices the sparse (value, index) entries at the int8
    wire's 8 bits/value while the unquantized LoRA projection keeps
    ``value_bits`` — the split :class:`repro.core.protocol.PayloadSpec`
    models with ``h_value_bits``."""
    rank = cfg.lora.rank if (send_h and cfg.lora is not None) else None
    spec = PayloadSpec(
        num_samples=num_samples, vocab=cfg.vocab_size, k=k,
        lora_rank=rank,
        value_bits=8 if quantize else value_bits,
        h_value_bits=value_bits if quantize else None,
    )
    return UplinkPayload(client_id=client_id, spec=spec, snr_db=snr_db), rank


@dataclasses.dataclass
class ClientUpload:
    client_id: int
    sparse: SparseLogits  # top-k (values, indices) on the public set
    h: jax.Array | None  # (P, r) LoRA projections (paper eq. 8)
    payload: UplinkPayload  # byte accounting
    k: int


class Client:
    def __init__(
        self,
        client_id: int,
        cfg: ModelConfig,
        private_data: IntentDataset,
        *,
        num_classes: int,
        seed: int = 0,
        lr: float = 1e-3,
        distill_lr: float = 1e-3,
        temperature: float = 2.0,
        lam: float = 0.03,
        batch_size: int = 32,
        local_steps: int = 4,
        distill_steps: int = 2,
        restrict_to_support: bool = False,
        initial_params=None,
    ):
        self.client_id = client_id
        self.cfg = cfg
        self.data = private_data
        self.num_classes = num_classes
        self.batch_size = batch_size
        self.local_steps = local_steps
        self.distill_steps = distill_steps
        if initial_params is not None:
            # shared pretrained backbone W' (paper eq. 1) + fresh LoRA delta
            import jax as _jax

            from repro.lora import merge_lora, split_lora

            own_lora, _ = split_lora(model_init(_jax.random.PRNGKey(seed), cfg))
            _, frozen = split_lora(initial_params)
            self.params = merge_lora(own_lora, frozen)
        else:
            self.params = model_init(jax.random.PRNGKey(seed), cfg)
        self.opt = fed_steps.init_lora_opt(self.params, cfg)
        self._train_step = fed_steps.make_finetune_step(cfg, num_classes, lr=lr)
        self._distill_step = fed_steps.make_distill_step(
            cfg, lr=distill_lr, temperature=temperature, lam=lam,
            restrict_to_support=restrict_to_support,
        )
        self._rng = np.random.default_rng(seed + 1000 * (client_id + 1))

    def next_train_batches(self, num_steps: int) -> list[dict]:
        """Draw the next ``num_steps`` private batches, advancing this
        client's RNG stream exactly as :meth:`local_train` consumes it — the
        batched engine pulls batches through here so both engines see
        identical data under the same seed."""
        out: list[dict] = []
        while len(out) < num_steps:
            for batch in epoch_batches(self.data, self.batch_size, rng=self._rng):
                out.append(batch)
                if len(out) >= num_steps:
                    break
        return out

    # ---- Algorithm 1, line 8: local supervised fine-tuning ----
    def local_train(self) -> dict:
        metrics = {}
        for batch in self.next_train_batches(self.local_steps):
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt, metrics = self._train_step(self.params, self.opt, jb)
        return {k: float(v) for k, v in metrics.items()}

    # ---- Algorithm 1, lines 5-7: local distillation vs global knowledge ----
    def local_distill(self, public_tokens, g_logits, g_h) -> dict:
        metrics = {}
        for _ in range(self.distill_steps):
            self.params, self.opt, metrics = self._distill_step(
                self.params, self.opt, public_tokens, g_logits, g_h
            )
        return {k: float(v) for k, v in metrics.items()}

    # ---- Algorithm 1, lines 9-11: infer public set, top-k, upload ----
    def upload(
        self,
        public_tokens: jax.Array,
        channel: ChannelState,
        *,
        value_bits: int = 16,
        k_override: int | None = None,
        send_h: bool = True,
        k_min: int = 1,
    ) -> ClientUpload | None:
        """Returns None when the round's budget yields ``k == 0`` — a
        straggler in outage transmits nothing and must not be zero-padded
        into aggregation.  That happens when the budget cannot afford a
        single (value, index) entry and ``k_min == 0``, OR (deep fade under
        ``send_h``) when the reserved projection bits alone exceed the
        Shannon budget: :func:`repro.core.channel.topk_budget` drops such a
        round entirely rather than emitting a ``k_min``-floored payload
        that cannot fit the link.

        With ``send_h`` the LoRA-projection bits ride on the same Shannon
        budget, so they are reserved out of it before the top-k entries are
        counted — the realized payload (projection included) then satisfies
        :meth:`repro.core.protocol.PayloadSpec.fits` by construction."""
        vocab = self.cfg.vocab_size
        n_samples = int(public_tokens.shape[0])
        if k_override is not None:
            k = int(min(k_override, vocab))
        else:
            reserved = (
                lora_projection_bits(n_samples, self.cfg.lora.rank, value_bits)
                if (send_h and self.cfg.lora is not None)
                else 0
            )
            k = topk_budget(
                channel, vocab_size=vocab, num_samples=n_samples,
                value_bits=value_bits, k_min=k_min, reserved_bits=reserved,
            )
        if k == 0:
            return None
        logits, h = fed_steps.public_logits(self.params, self.cfg, public_tokens)
        sparse = topk_sparsify(logits, k)
        payload, _ = make_upload_payload(
            self.cfg, self.client_id, n_samples, k,
            send_h=send_h, value_bits=value_bits, snr_db=channel.snr_db,
        )
        return ClientUpload(
            client_id=self.client_id,
            sparse=sparse,
            h=h if send_h else None,
            payload=payload,
            k=k,
        )
