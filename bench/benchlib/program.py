"""The program under test, as the benchmark sees it: its registered
architecture for a configuration file, checked against every size the
file states, and the check that the bench's weights fit its layout."""
from __future__ import annotations

import dataclasses

from benchlib.spec import load_reference


def program_config(c: dict):
    """The program's registered architecture for this configuration,
    checked against every field the configuration's module derives from
    the file (``program_fields``)."""
    from repro.config import get_arch
    cfg = get_arch(c["program_arch"])
    want = load_reference(c).program_fields(c)
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
