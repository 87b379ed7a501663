"""What `build_train_step` asks of a model, written down once.

A model is any `Layer` made of blocks around an embedding and a loss
head, the blocks an ordered list of GROUPS of alike blocks (a leading
dense layer, then the expert layers; or one group, where every block is
alike). It gives the builder:

  * `config`, with `dropout`;
  * `block_groups()`: `[(template block, number of blocks), ...]` in the
    order the blocks are applied. The builder applies each group's
    template to that group's stacked leaves `[n, ...]` under a scan of
    its own, every group under the same remat policy, scope and streams.
    The blocks are the parameters named `<prefix>.layers.<i>.<rel>` (a
    `LayerList` called `layers`), alike in every `i` of one group, the
    groups taking the indices in order; every other trainable parameter
    is "outer". A model whose blocks are all alike gives one group;
  * `embed(input_ids, position_ids)`, `final_norm(hidden)`,
    `logits(hidden)`;
  * `criterion(logits, labels)`, whose `.ce(logits, labels)` is the loss
    of each position (the chunked loss head sums it itself);
  * optionally `step_name`, the compiled step's module name.

The builder reads nothing else of a model, and no model file knows the
builder. What a block gives back beside its output are counters: traced
int32 values it hands to `profiler.count` (the expert layer's rows and
rounds, `moe.py MoEMLP.forward`), which the trunk stacks by block and the
step returns beside the loss, for `profiler.step_records()`.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ..nn.layer import Layer, Parameter, load_state

Groups = List[Tuple[Layer, int]]


def block_groups(model: Layer) -> Groups:
    """The model's groups of alike blocks, `[(template, blocks), ...]`."""
    return [(t, int(n)) for t, n in model.block_groups()]


def group_keys(groups: Groups) -> List[str]:
    """What a group's leaves are called in the stacked state, before
    `rel`: nothing for a one-group model (`{rel: [L, ...]}`, the layout
    every reader of such a state knows), `g<i>.` where there are more."""
    if len(groups) == 1:
        return [""]
    return [f"g{i}." for i in range(len(groups))]


def _group_of(index: int, groups: Groups) -> Tuple[int, int]:
    """`(group, place in it)` of block `index`."""
    for g, (_, n) in enumerate(groups):
        if index < n:
            return g, index
        index -= n
    raise IndexError(f"block {index} beyond the model's groups")


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
            "config.dropout": hasattr(config, "dropout")}
    for method in ("block_groups", "embed", "final_norm", "logits",
                   "criterion"):
        have[method + "()"] = callable(getattr(model, method, None))
    have["criterion.ce()"] = callable(
        getattr(getattr(model, "criterion", None), "ce", None))
    missing = [piece for piece, there in have.items() if not there]
    if missing:
        raise TypeError(
            f"{type(model).__name__} cannot go through build_train_step: "
            f"it has no {', '.join(missing)} (the contract, groups of "
            f"alike blocks around an embedding and a loss head: "
            f"paddle_tpu/trainer/contract.py)")
    indices = {b[0] for b in map(_block_of, (
        n for n, _ in model.named_parameters())) if b}
    if not indices:
        raise TypeError(
            f"{type(model).__name__} cannot go through build_train_step: "
            f"it has no blocks to stack (the parameters of a `LayerList` "
            f"called `layers`)")
    total = sum(n for _, n in block_groups(model))
    if indices != set(range(total)):
        raise TypeError(
            f"{type(model).__name__} cannot go through build_train_step: "
            f"its groups hold {total} blocks and its `layers` "
            f"{len(indices)}")


def split_parameters(model: Layer) -> Tuple[Dict[str, Parameter],
                                            List[Dict[str, Parameter]]]:
    """The model's trainable parameters as `(outer: {name: p}, blocks:
    [{rel: p} for each block])`, `rel` keyed to its group's template."""
    outer: Dict[str, Parameter] = {}
    blocks: List[Dict[str, Parameter]] = [
        {} for _ in range(sum(n for _, n in block_groups(model)))]
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
    groups = block_groups(model)
    keys = group_keys(groups)
    flat = dict(outer_p)
    for name, _ in model.named_parameters():
        block = _block_of(name)
        if block is None:
            continue
        g, i = _group_of(block[0], groups)
        if keys[g] + block[1] in stacked_p:
            flat[name] = stacked_p[keys[g] + block[1]][i]
    load_state(model, flat)
