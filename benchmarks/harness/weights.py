"""The benchmark's weights into the program's model, the way a user loads
a checkpoint: `Layer.set_state_dict`. The reference draws the weights
(stacked over layers, "blocks.<kind>" [L, ...]); the program keeps one
leaf per layer, so `names` maps every reference leaf id
("blocks.<i>.<kind>" or an outer name) to the program's parameter name.
"""
from __future__ import annotations


def layer_names(outer: dict, block: dict, n_layer: int, prefix: str) -> dict:
    out = dict(outer)
    for i in range(n_layer):
        out.update({f"blocks.{i}.{c}": f"{prefix}.{i}.{n}"
                    for c, n in block.items()})
    return out


def load(model, weights: dict, names: dict) -> None:
    import jax

    @jax.jit
    def spread(w):
        out = {}
        for c, n in names.items():
            parts = c.split(".")
            out[n] = (w["blocks." + ".".join(parts[2:])][int(parts[1])]
                      if parts[0] == "blocks" else w[c])
        return out

    missing, unexpected = model.set_state_dict(spread(weights))
    if missing or unexpected:
        raise ValueError("the program's parameters are not the ones the "
                         f"adapter maps: missing {missing}, unexpected "
                         f"{unexpected}")
