"""repro: overlay-aware decentralized learning framework (JAX/TPU).

Reproduction + extension of "Communication Optimization for Decentralized
Learning atop Bandwidth-limited Edge Networks" (Sun, Nguyen, He; 2025).

Layers:
  repro.core      — mixing-matrix design (FMMD family), D-PSGD, gossip collectives
  repro.net       — underlay/overlay network model, categories, routing (MILP + heuristic)
  repro.models    — assigned LM architectures (dense/MoE/SSM/hybrid/audio/VLM backbones)
  repro.data      — synthetic non-IID data pipeline
  repro.optim     — optimizers and schedules
  repro.checkpoint— checkpoint/restore
  repro.runtime   — fault tolerance, stragglers, compression
  repro.kernels   — Pallas TPU kernels (flash attention, mixing combine)
  repro.launch    — mesh, dry-run, the distributed train step
  repro.roofline  — roofline analysis from compiled artifacts
"""

__version__ = "1.0.0"
