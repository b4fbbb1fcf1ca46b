"""The system under test for an MLA + MoE configuration (DeepSeek-V3): this
repository's ``Model`` and ``ServingEngine``, built from a configuration
file, holding the configuration's share of the experts.

The benchmark makes the weights (``refs/<reference>.make_weights``) and
this module places them where the program keeps them; it changes no
number.  It takes nothing else from the program than its entry points.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[3]

# reference leaf -> path inside one block of the program's tree
ATTN_PATHS = {
    "ln1": ("ln1", "w"), "wdq": ("attn", "dq", "w"), "q_norm": ("attn", "q_norm", "w"),
    "wuq": ("attn", "uq", "w"), "wdkv": ("attn", "dkv", "w"),
    "kv_norm": ("attn", "kv_norm", "w"), "wuk": ("attn", "uk", "w"),
    "wuv": ("attn", "uv", "w"), "wkr": ("attn", "kr", "w"), "wo": ("attn", "o", "w"),
    "ln2": ("ln2", "w"),
}
LEAD_PATHS = dict(ATTN_PATHS, wg=("mlp", "gate", "w"), wu=("mlp", "up", "w"),
                  wd=("mlp", "down", "w"))
MOE_PATHS = dict(ATTN_PATHS, router=("moe", "router", "w"),
                 router_bias=("moe", "router", "bias"), eg=("moe", "experts", "gate"),
                 eu=("moe", "experts", "up"), ed=("moe", "experts", "down"),
                 sg=("moe", "shared", "gate", "w"), su=("moe", "shared", "up", "w"),
                 sd=("moe", "shared", "down", "w"))


def _import_program():
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    config of ``arch`` with every size the file states put in, and the
    file's share of the experts; refuses one whose layer the program would
    build otherwise."""
    _import_program()
    from repro.configs import get_config
    c = conf["config"]
    share = c.get("expert_share", {})
    rs = c["rope_scaling"]
    mc = get_config(conf["arch"]).replace(
        num_layers=c["num_hidden_layers"], first_k_dense=c["first_k_dense_replace"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        num_experts=share.get("router_experts", c["n_routed_experts"]),
        num_experts_held=c["n_routed_experts"], expert_offset=share.get("expert_offset", 0),
        top_k=c["num_experts_per_tok"], num_shared_experts=c["n_shared_experts"],
        moe_d_ff=c["moe_intermediate_size"], n_group=c["n_group"], topk_group=c["topk_group"],
        routed_scaling_factor=c["routed_scaling_factor"],
        rope_theta=c["rope_theta"], yarn_factor=rs["factor"], yarn_beta_fast=rs["beta_fast"],
        yarn_beta_slow=rs["beta_slow"], yarn_mscale_all_dim=rs["mscale_all_dim"],
        yarn_original_max_pos=rs["original_max_position_embeddings"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["torch_dtype"], dtype=conf["compute_dtype"])
    layer = {"family": "moe", "attention": "mla", "act": "swiglu", "norm": "rmsnorm",
             "rope_style": "standard", "qkv_bias": False, "window": 0,
             "logit_soft_cap": 0.0, "rms_offset": False, "scale_embedding": False,
             "router": "noaux_tc"}
    wrong = {k: getattr(mc, k) for k, v in layer.items() if getattr(mc, k) != v}
    published = {"hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "moe_layer_freq": 1, "attention_bias": False, "num_nextn_predict_layers": 0,
                 "norm_topk_prob": True}
    wrong.update({k: c[k] for k, v in published.items() if c[k] != v})
    if rs.get("type") != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        wrong["rope_scaling"] = rs
    if wrong:
        raise ValueError(f"{conf['arch']}: the program builds another layer: {wrong}")
    return mc


def _nest(leaves: dict, paths: dict) -> dict:
    block: dict = {}
    for name, path in paths.items():
        node = block
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaves[name]
    return block


def _pick(block: dict, paths: dict) -> dict:
    out = {}
    for name, path in paths.items():
        node = block
        for p in path:
            node = node[p]
        out[name] = node
    return out


def to_program(w: dict) -> dict:
    """Reference-layout weights -> the program's parameter tree."""
    blocks = {"cycle": [_nest(w["moe"], MOE_PATHS)], "tail": []}
    if w["lead"]:
        blocks["lead"] = [_nest(lw, LEAD_PATHS) for lw in w["lead"]]
    return {"embed": {"w": w["embed"]}, "lm_head": {"w": w["head"]},
            "final_norm": {"w": w["norm"]}, "blocks": blocks}


def from_program(tree: dict) -> dict:
    """The program's parameter tree -> reference layout."""
    return {"embed": tree["embed"]["w"], "head": tree["lm_head"]["w"],
            "norm": tree["final_norm"]["w"],
            "lead": [_pick(b, LEAD_PATHS) for b in tree["blocks"].get("lead", [])],
            "moe": _pick(tree["blocks"]["cycle"][0], MOE_PATHS)}


def check_layout(mc, weights_shape: dict) -> None:
    """The program's own parameter shapes must be those the weights fill."""
    _import_program()
    from repro.models import Model
    want = jax.eval_shape(Model(mc).init, jax.random.PRNGKey(0))
    got = to_program(weights_shape)
    w_s = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    g_s = jax.tree.map(lambda x: (x.shape, str(x.dtype)), got)
    if w_s != g_s:
        raise ValueError(f"parameter layout differs from the program's: {g_s} vs {w_s}")


def engine(mc, params, *, slots: int, cache_len: int):
    _import_program()
    from repro.serving import ServingEngine
    return ServingEngine(mc, params, slots=slots, cache_len=cache_len)


def request(rid: int, prompt: list[int], max_new_tokens: int, arrival_s: float):
    _import_program()
    from repro.serving import Request
    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                   arrival_s=arrival_s)


def predict(mc, device_kind: str, workload: str, **shape) -> float:
    """The simulator's predicted decode step time (s) on the chip of
    ``device_kind`` (``batch``, ``cache_len``)."""
    _import_program()
    from repro.api import Cluster, DecodeWorkload, SimSpec
    from repro.core import Simulator
    from repro.core.backend.hardware import hardware_for_device_kind
    from repro.core.backend.profiling import ProfileDB
    if workload != "decode":
        raise ValueError(f"no {workload!r} step for a serving-only configuration")
    hw = hardware_for_device_kind(device_kind)
    wl = DecodeWorkload(global_batch=shape["batch"], seq_len=shape["cache_len"],
                        cache_len=shape["cache_len"])
    rep = Simulator(hw, db=ProfileDB(None)).run(SimSpec(mc, Cluster(hw), workload=wl))
    return rep.step_time_us * 1e-6
