"""One module per family of models: ``perfbench/archs/<arch>.py`` holds what is
true of that family and of no other: its shape, its weights, its plain
reference, the tree the program serves and its arithmetic. A configuration
file names its module with the key ``"arch"``: a name under this directory,
or a path that ends in ``.py`` (relative to the configuration file; the tests
bring one so). A ``model_config`` PR adds a module and edits no file.

What a module gives, and all that the generic files may ask of it (checked
when the module is loaded; a module that lacks a name fails there, by name):

``model_from_config(cfg) -> Model``
    A frozen, hashable dataclass of the module's own (the reference caches
    jitted functions on it). Generic code reads only ``name`` and ``vocab``
    from it. How many stacks of layers there are, of what kinds, and what
    share of a layer this chip holds (experts held of experts routed over, a
    slice of the vocabulary) are the module's own fields.
``make_stack(m, seed)``
    Every weight from ``--seed`` in one jitted call, each leaf from its own
    ``fold_in`` chain (``weights.seed_key``, ``weights._qleaf``,
    ``weights._norm_scale`` are there to import).
``program_config(cfg, m)``, ``program_params(m, seed, int8)``
    The program's ``ModelConfig`` to register, and the tree the program
    serves. ``serving.py`` calls these two and knows no field of either; only
    these two functions import the program, and only when called.
``logits_at(m, seed, sequences, n_last, mode) -> [(logits, margin), ...]``
    For each token sequence the logits ``[n_last[i], vocab]`` at its last
    ``n_last[i]`` positions, float32 at ``highest``, by the module's own
    forward pass with the weights made one layer at a time; ``margin
    [n_last[i]]`` is the least lead, over the layers, by which the router at
    that position chose its last expert over the next, ``inf`` where the model
    has no router. The modes ``f32``, ``act8`` and ``w4`` (see
    ``reference.py``) are the module's to honour; ``reference._deq``,
    ``_mm``, ``_rms`` and ``_rope`` are there to import.
    ``reference.served_gaps`` (teacher forcing, the tie filter, the gaps, the
    control's reading) is generic and calls this.
``attn_params(m)``, ``mlp_params_one(m)``, ``params_held(m)``,
``params_active(m, with_head=True)``, ``attention_flops(m, context_sum)``,
``request_flops(m, prompt, output, cached_prefix=0)``,
``decode_step_weight_bytes(m)``, ``flash_prefill_flops(m, context_sum)``,
``flash_prefill_bytes(m, q_tokens)``
    Operations and bytes the model *requires*, from its shapes alone.
    ``shapes.py`` hands on to them under the same names, so the metric
    readers are the same for every family. ``context_sum`` is the sum over
    queries of the keys each sees under full causal attention, as
    ``readers.traced_prefill_context`` lays it into the traced slice.
``READS``
    The configuration keys the module takes its shape from.
``IGNORES``
    ``{key: reason}`` for published keys that change nothing the module
    computes. ``weights.load_config`` fails on a key that is in neither and
    that the harness does not know (``HARNESS_KEYS``): a published key that
    changes the mathematics cannot be dropped in silence.
"""

from __future__ import annotations

import importlib
import importlib.util
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, List

HERE = Path(__file__).resolve().parent

REQUIRED = (
    "model_from_config", "make_stack", "program_config", "program_params", "logits_at",
    "attn_params", "mlp_params_one", "params_held", "params_active", "attention_flops",
    "request_flops", "decode_step_weight_bytes", "flash_prefill_flops",
    "flash_prefill_bytes", "READS", "IGNORES",
)

# Keys of a configuration file that are the harness's own.
HARNESS_KEYS = (
    "name", "source", "arch", "assumed", "reduced_from_source", "deployment", "precision",
    "serve",
)


def found() -> List[str]:
    """The modules under this directory, by the name a configuration gives."""
    return sorted(p.stem for p in HERE.glob("*.py") if not p.stem.startswith("_"))


def _from_path(path: Path) -> ModuleType:
    """A module outside this directory, imported once under a name made
    from its path."""
    name = "perfbench_arch_" + "_".join(path.with_suffix("").parts[1:])
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise ValueError(f"no architecture module at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module      # dataclasses look their module up here
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def load(arch: Any) -> ModuleType:
    """The module a configuration's ``arch`` names, checked against the list
    above."""
    names = found()
    if not isinstance(arch, str) or not arch:
        raise ValueError(
            f"a configuration names its architecture with the key \"arch\": one of {names} "
            "(perfbench/archs/<arch>.py) or a path that ends in .py")
    if arch.endswith(".py"):
        module = _from_path(Path(arch).resolve())
    elif arch in names:
        module = importlib.import_module(f"perfbench.archs.{arch}")
    else:
        raise ValueError(f"unknown arch {arch!r}: perfbench/archs/ has {names}")
    lacking = [n for n in REQUIRED if not hasattr(module, n)]
    if lacking:
        raise AttributeError(
            f"architecture module {module.__name__} lacks {lacking}: see the list in "
            "perfbench/archs/__init__.py")
    return module


def of(model: Any) -> ModuleType:
    """The module whose ``model_from_config`` made this model."""
    return sys.modules[type(model).__module__]


def unread_keys(cfg: dict, module: ModuleType) -> List[str]:
    known = set(HARNESS_KEYS) | set(module.READS) | set(module.IGNORES)
    return sorted(k for k in cfg if k not in known)
