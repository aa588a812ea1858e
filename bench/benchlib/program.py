"""The program under test, as the benchmark sees it: its registered
architecture for a configuration file, checked against every size the
file states, and the check that the bench's weights fit its layout."""
from __future__ import annotations

import dataclasses


def program_config(c: dict):
    """The program's registered architecture for this configuration,
    checked against every size the file states."""
    from repro.config import get_arch
    cfg = get_arch(c["program_arch"])
    want = {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"], "dtype": c["dtype"],
            "vocab_pad_to": c["vocab_pad_to"],
            "norm_type": {"nonparam_layernorm": "nonparam_ln",
                          "rmsnorm": "rmsnorm"}[c["norm"]]}
    if c.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **c["program_overrides"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {c['program_arch']} differs from "
                         f"{c['name']}: {got} != {want}")
    return cfg


def check_layout(cfg, params) -> None:
    """The bench's weights have the tree, shapes and dtypes the program's
    own initializer would give."""
    import jax
    from repro.models import transformer as tf
    want = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    sd = lambda t: jax.tree.map(  # noqa: E731
        lambda x: (tuple(x.shape), str(x.dtype)), t)
    if sd(want) != sd(params):
        raise ValueError("bench weights do not match the program's layout")
