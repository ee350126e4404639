from .kernel import flash_attention_fwd
from .ops import flash_attention_train
from .ref import attention_ref

__all__ = ["flash_attention_fwd", "flash_attention_train", "attention_ref"]
