"""Model zoo (parity with python/mxnet/gluon/model_zoo): the vision families,
``TransformerLM`` (GPT-2's block) and, imported on first use,
``HybridDecoderLM`` (decoder-hybrid-decoder: Mamba, differential attention,
gated memory units; ``hybrid_decoder.py``)."""

from . import model_store, transformer, vision
from .transformer import TransformerLM, transformer_lm
from .vision import get_model


def __getattr__(name):
    # the hybrid family comes in when it is asked for: ``import mxtpu`` and
    # the models that do not use it import nothing of it
    if name in ("hybrid_decoder", "HybridDecoderLM"):
        import importlib
        mod = importlib.import_module(".hybrid_decoder", __name__)
        return mod if name == "hybrid_decoder" else mod.HybridDecoderLM
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
