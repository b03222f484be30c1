"""The served configurations, a module a runner, and THE table of which
runner serves which decoder Layer.

Adding a served configuration that brings no new cache mechanism is
`models/<name>.py`, `serving/runners/<name>.py` (a `PagedModelRunner`
subclass: what its programs count is its `COUNTS`, under names of its own
that nothing above it spells), its kernel, and one line here. Nothing is
imported until a model asks for its runner.
"""

from __future__ import annotations

from importlib import import_module

# (the Layer's class, its runner's class, what the Layer is), each class as
# "module:name"; a model is served by the first line whose Layer it is
RUNNERS = (
    ("paddle_tpu.models.llama:Llama",
     "paddle_tpu.serving.runners.llama:LlamaRunner", ""),
    ("paddle_tpu.models.gpt:GPT",
     "paddle_tpu.serving.runners.gpt:GPTRunner", ""),
    ("paddle_tpu.models.deepseek_v3:DeepseekV3ForCausalLM",
     "paddle_tpu.serving.runners.deepseek_v3:DeepseekV3Runner",
     "DeepSeek-V3, Kimi K2, and DeepSeek-V3.2 where its configuration sets "
     "index_topk"),
    ("paddle_tpu.models.olmo_hybrid:OlmoHybridForCausalLM",
     "paddle_tpu.serving.runners.olmo_hybrid:OlmoHybridRunner", ""),
    ("paddle_tpu.models.phi4flash:Phi4FlashForCausalLM",
     "paddle_tpu.serving.runners.phi4flash:Phi4FlashRunner", ""),
    ("paddle_tpu.models.laguna:LagunaForCausalLM",
     "paddle_tpu.serving.runners.laguna:LagunaRunner",
     "Laguna-XS.2: every expert held"),
)


def _named(path: str):
    module, _, name = path.partition(":")
    return getattr(import_module(module), name)


def runner_class(model):
    """The runner class that serves `model`, or None."""
    for layer, runner, _ in RUNNERS:
        if isinstance(model, _named(layer)):
            return _named(runner)
    return None


def supported() -> str:
    """The Layers the table serves, for a message or a docstring."""
    return ", ".join(layer.partition(":")[2] + (f" ({what})" if what else "")
                     for layer, _, what in RUNNERS)
