"""Phi-3 (``model_type`` ``phi3``): the program's schema splits its fused
``qkv_proj`` and ``gate_up_proj`` into the Llama block's linears, so the
block, its reference and its FLOP count are Llama's."""
from perfbench.arch.llama import NORMS, Reference, model_config, step_flops

__all__ = ["NORMS", "Reference", "model_config", "step_flops"]
