"""Model zoo: the assigned architectures over shared substrate layers."""
from repro.models.config import ModelConfig
from repro.models.registry import build_model, get_model, list_archs, reduced_config
from repro.models.transformer import CausalLM
from repro.models.encdec import EncDecLM

__all__ = ["ModelConfig", "build_model", "get_model", "list_archs",
           "reduced_config", "CausalLM", "EncDecLM"]
