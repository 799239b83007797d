"""Architecture families, one file each: ``bench/families/<family>.py``
under the checkout's root, named by a configuration file's ``family``.

A family module gives, each function taking the whole configuration
file (``model`` holds the sizes as run, ``reduced`` what was cut):

- ``dims(conf)``: the sizes the forward pass and the metric readers
  need, with at least ``d``, ``h``, ``hkv``, ``dh``, ``layers``,
  ``vocab`` and ``eps``;
- ``layout(conf, tp, dtype)``: the parameter tree the program must
  hold, as ``ShapeDtypeStruct``s;
- ``Reference(conf, seed)`` with ``.hidden(seqs, rows, precision)`` and
  ``.logits_stats(hs, tokens, precision)``; ``precision`` is ``"f32"``
  or ``"fp8"``, the control;
- ``model_flops(m, new_tokens, attended, logit_rows)``: the work count,
  ``m`` being ``dims(conf)``;
- ``arch_config(conf)``: the program's ``ArchConfig``, the one function
  that imports the program, inside its body.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

_LOADED: dict = {}      # path -> module, so a family's jitted functions
                        # keep their compiled programs across calls


def path(root: Path, conf: dict) -> Path:
    """The family file a configuration names; an error where it names
    none or one that does not exist."""
    name = conf.get("family")
    if name is None:
        raise KeyError(f"configuration {conf.get('name')!r} names no "
                       f"family: give \"family\", the <family> of a "
                       f"file bench/families/<family>.py")
    p = Path(root) / "bench" / "families" / f"{name}.py"
    if not p.is_file():
        raise FileNotFoundError(f"configuration {conf.get('name')!r} names "
                                f"family {name!r}, but there is no {p}")
    return p


def load(root: Path, conf: dict):
    """The family module of a configuration, loaded once per file."""
    p = path(root, conf).resolve()
    if p not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_family_{p.stem}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[p] = mod
    return _LOADED[p]
