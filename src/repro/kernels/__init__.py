"""Device programs for the compute hot-spots.

* slot_alloc       — the CCU's compiled prepare program (the paper's
                     PE-matrix TDM slot search, slot choice and
                     trace-back in one jit; fused.py) and its numpy
                     oracle (ref.py)

Three Pallas TPU kernels, each with ops.py (jit'd wrapper) and ref.py
(pure-jnp oracle), validated in interpret mode; no model layer calls
them yet:

* flash_attention  — causal/sliding-window GQA flash attention (fwd)
* ssd_scan         — Mamba-2 SSD chunked scan
* rglru_scan       — RecurrentGemma RG-LRU linear recurrence"""
