"""Share of the matrix units' peak the held experts' grouped products reach:
their least FLOPs a step / the chip's peak bf16 FLOP/s /
``tok_expert_products_ms``. Least: ``3 x 2 x held pairs x hidden x width x
the arrays an expert has`` (three for a gated SiLU, two for a squared ReLU):
forward and both gradients of every product once over the token-expert pairs
the held experts computed in the newest step, all expert layers together (the
step's own count ``moe_pairs``, ``trainer.last_metrics``), at the published
width and not ``_whole_tiles``' padded one; the prefix's empty rows, the
padding and the remat's second forward are executed and not counted, so the
share cannot pass 100%. None from a program that does not count or has no
part scopes."""

from chipbench.harness import token_parts


def least_flops_per_step(model: dict, held_pairs: float) -> float:
    arrays = 2 if model.get("mlp_hidden_act") == "relu2" else 3
    return 3 * 2.0 * held_pairs * int(model["hidden_size"]) * int(
        model["moe_intermediate_size"]) * arrays


def read(context):
    metrics = getattr(context["trainer"], "last_metrics", None) or {}
    if "moe_pairs" not in metrics or token_parts.split(context) is None:
        return None
    return token_parts.roofline_pct(context, "expert_products", least_flops_per_step(
        context["cell"].model, float(metrics["moe_pairs"])))
