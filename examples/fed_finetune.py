"""End-to-end driver (deliverable b): federated fine-tuning of a ~language
model family for a few hundred steps, reproducing the paper's experiment
shape — 4 methods, accuracy + exact communication accounting.

Each round runs clients_per_round x (local_steps + distill_steps) model
updates plus server distillation; 12 rounds x 4 clients x 8 steps ≈ 400+
optimisation steps end-to-end.

Run:  PYTHONPATH=src python examples/fed_finetune.py [rounds] [engine]

``engine`` is ``batched`` (default: the whole selected cohort advances as
single vmapped/jitted per-phase steps), ``fused`` (the entire client phase
— distill, fine-tune, public inference, adaptive top-k — as ONE donated
jitted call per round), ``fused_e2e`` (the WHOLE round — client phase plus
sparse-wire aggregation, server distillation and broadcast — as one
compiled call) or ``sequential`` (the bit-compatible one-client-at-a-time
reference) — see FedConfig.engine.  All engines compute the LM head on
the last position only.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.gpt2_paper import REDUCED_CLIENT, REDUCED_SERVER  # noqa: E402
from repro.data import make_banking77_like  # noqa: E402
from repro.fed import FedConfig, run_federated  # noqa: E402

rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 12
engine = sys.argv[2] if len(sys.argv) > 2 else "batched"

client_cfg = REDUCED_CLIENT
server_cfg = REDUCED_SERVER
dataset = make_banking77_like(vocab_size=client_cfg.vocab_size, seq_len=24, seed=0)

print(f"clients: {client_cfg.name} ({client_cfg.param_count()/1e6:.1f}M params)  "
      f"server: {server_cfg.name} ({server_cfg.param_count()/1e6:.1f}M params)  "
      f"engine: {engine}")

results = {}
for method in ("adald", "zeropad"):
    fed = FedConfig(
        method=method, engine=engine, num_clients=10, clients_per_round=4, rounds=rounds,
        public_size=512, public_batch=96, eval_size=512,
        local_steps=6, distill_steps=2, seed=0,
    )
    print(f"\n=== {method} ===")
    run = run_federated(client_cfg, server_cfg, dataset, fed, verbose=True)
    results[method] = run
    print(f"{method}: best server acc {max(run.server_acc):.3f}, "
          f"uplink {run.ledger.uplink_mb:.2f} MB")

a, z = results["adald"], results["zeropad"]
print("\n=== comparison (paper Fig. 2 ordering) ===")
print(f"AdaLD   best={max(a.server_acc):.3f}  uplink={a.ledger.uplink_mb:.2f} MB")
print(f"ZeroPad best={max(z.server_acc):.3f}  uplink={z.ledger.uplink_mb:.2f} MB")
