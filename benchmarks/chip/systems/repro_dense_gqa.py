"""The system under test for a dense GQA configuration: this repository's
``Model``, ``ServingEngine`` and train step, built from a configuration file.

The benchmark makes the weights (``refs/<reference>.make_weights``) and
this module places them where the program keeps them; it changes no
number.  It takes nothing else from the program than its entry points.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[3]

# reference leaf -> path inside one stacked block of the program's tree
LAYER_PATHS = {
    "ln1": ("ln1", "w"), "wq": ("attn", "q", "w"), "wk": ("attn", "k", "w"),
    "wv": ("attn", "v", "w"), "wo": ("attn", "o", "w"), "ln2": ("ln2", "w"),
    "wg": ("mlp", "gate", "w"), "wu": ("mlp", "up", "w"), "wd": ("mlp", "down", "w"),
}


def _import_program():
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    config of ``arch`` with every size the file states put in; refuses one
    whose layer the program would build otherwise."""
    _import_program()
    from repro.configs import get_config
    c = conf["config"]
    mc = get_config(conf["arch"]).replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_fraction=c["partial_rotary_factor"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], param_dtype=c["torch_dtype"],
        dtype=conf["compute_dtype"])
    layer = {"family": "dense", "attention": "gqa", "act": "swiglu", "norm": "rmsnorm",
             "rope_style": "partial", "qkv_bias": False, "window": 0,
             "logit_soft_cap": 0.0, "rms_offset": False, "scale_embedding": False}
    wrong = {k: getattr(mc, k) for k, v in layer.items() if getattr(mc, k) != v}
    if wrong or c["hidden_act"] != "silu":
        raise ValueError(f"{conf['arch']}: the program builds another layer: {wrong}")
    return mc


def to_program(w: dict) -> dict:
    """Reference-layout weights -> the program's parameter tree."""
    block: dict = {}
    for name, path in LAYER_PATHS.items():
        node = block
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = w[name]
    return {"embed": {"w": w["embed"]}, "final_norm": {"w": w["norm"]},
            "blocks": {"cycle": [block], "tail": []}}


def from_program(tree: dict) -> dict:
    """The program's parameter tree (or a tree shaped like it, such as an
    AdamW moment) -> reference layout."""
    block = tree["blocks"]["cycle"][0]
    out = {"embed": tree["embed"]["w"], "norm": tree["final_norm"]["w"]}
    for name, path in LAYER_PATHS.items():
        node = block
        for p in path:
            node = node[p]
        out[name] = node
    return out


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


def train_step(mc, mix: dict):
    """The step as ``launch/train.py`` builds it: ``make_train_step``
    jitted with the state donated.  Returns (step, optimizer)."""
    _import_program()
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.training.optimizer import adamw, cosine_schedule
    from repro.training.train_step import make_train_step
    o = mix["optimizer"]
    opt = adamw(cosine_schedule(o["peak_lr"], warmup=o["warmup"], total=o["total"],
                                final_frac=o["final_frac"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    run = RunConfig(model=mc, shape=ShapeConfig("bench", mix["seq"], mix["batch"], "train"),
                    remat_policy=mix["remat"])
    return jax.jit(make_train_step(mc, run, opt), donate_argnums=(0,)), opt


def predict(mc, device_kind: str, workload: str, **shape) -> float:
    """The simulator's predicted step time (s) on the chip of
    ``device_kind`` for a decode step (``batch``, ``cache_len``) or a train
    step (``batch``, ``seq``, ``remat``)."""
    _import_program()
    from repro.api import Cluster, DecodeWorkload, SimSpec, TrainWorkload
    from repro.core import Simulator
    from repro.core.backend.hardware import hardware_for_device_kind
    from repro.core.backend.profiling import ProfileDB
    hw = hardware_for_device_kind(device_kind)
    if workload == "decode":
        wl = DecodeWorkload(global_batch=shape["batch"], seq_len=shape["cache_len"],
                            cache_len=shape["cache_len"])
    else:
        wl = TrainWorkload(global_batch=shape["batch"], seq_len=shape["seq"],
                           remat=shape["remat"], optimizer="adamw")
    rep = Simulator(hw, db=ProfileDB(None)).run(SimSpec(mc, Cluster(hw), workload=wl))
    return rep.step_time_us * 1e-6
