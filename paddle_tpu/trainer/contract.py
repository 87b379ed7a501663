"""What `build_train_step` asks of a model, written down once.

A model is any `Layer` made of uniform blocks around an embedding and a
loss head. It gives the builder:

  * `config`, with `num_layers` and `dropout`;
  * `block_template()`: one block, which the builder applies to stacked
    leaves `[L, ...]` under a scan. The blocks are the parameters named
    `<prefix>.layers.<i>.<rel>` (a `LayerList` called `layers`), alike in
    every `i`; every other trainable parameter is "outer";
  * `embed(input_ids, position_ids)`, `final_norm(hidden)`,
    `logits(hidden)`;
  * `criterion(logits, labels)`, whose `.ce(logits, labels)` is the loss
    of each position (the chunked loss head sums it itself);
  * optionally `step_name`, the compiled step's module name.

The builder reads nothing else of a model, and no model file knows the
builder.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ..nn.layer import Layer, Parameter, load_state


def _block_of(name: str) -> Optional[Tuple[int, str]]:
    """`(i, rel)` of a block's parameter `<prefix>.layers.<i>.<rel>` (or
    `layers.<i>.<rel>`, of a model that holds its blocks itself), None of
    any other parameter: the naming rule, in this one place."""
    _, found, rest = ("." + name).partition(".layers.")
    if not found:
        return None
    index, rel = rest.split(".", 1)
    return int(index), rel


def check_model(model: Layer) -> None:
    """Refuse a model that lacks a piece of the contract above, by the
    piece's name."""
    config = getattr(model, "config", None)
    have = {"config": config is not None,
            "config.num_layers": hasattr(config, "num_layers"),
            "config.dropout": hasattr(config, "dropout")}
    for method in ("block_template", "embed", "final_norm", "logits",
                   "criterion"):
        have[method + "()"] = callable(getattr(model, method, None))
    have["criterion.ce()"] = callable(
        getattr(getattr(model, "criterion", None), "ce", None))
    missing = [piece for piece, there in have.items() if not there]
    if missing:
        raise TypeError(
            f"{type(model).__name__} cannot go through build_train_step: "
            f"it has no {', '.join(missing)} (the contract: "
            f"paddle_tpu/trainer/contract.py)")
    if not any(_block_of(n) for n, _ in model.named_parameters()):
        raise TypeError(
            f"{type(model).__name__} cannot go through build_train_step: "
            f"it has no blocks to stack (the parameters of a `LayerList` "
            f"called `layers`)")


def split_parameters(model: Layer) -> Tuple[Dict[str, Parameter],
                                            List[Dict[str, Parameter]]]:
    """The model's trainable parameters as `(outer: {name: p}, blocks:
    [{rel: p} for each block])`, `rel` keyed to one template block."""
    outer: Dict[str, Parameter] = {}
    blocks: List[Dict[str, Parameter]] = [
        {} for _ in range(model.config.num_layers)]
    for name, p in model.named_parameters():
        if not p.trainable:
            continue
        block = _block_of(name)
        if block is None:
            outer[name] = p
        else:
            blocks[block[0]][block[1]] = p
    return outer, blocks


def sync_params_to_model(model: Layer, state: Tuple[Dict[str, Any],
                                                    Dict[str, Any], Any]):
    """Write a state's `(outer, stacked)` back into the Layer tree (for
    save / eval): `split_parameters` and the stacking, undone."""
    outer_p, stacked_p, _ = state
    flat = dict(outer_p)
    for name, _ in model.named_parameters():
        block = _block_of(name)
        if block is not None and block[1] in stacked_p:
            flat[name] = stacked_p[block[1]][block[0]]
    load_state(model, flat)
