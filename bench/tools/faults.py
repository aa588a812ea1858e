"""Faults planted in the training program's timed path, for the tests
that see a broken step come out not correct and for the readings on the
chip that set the upper end of each limit.

Each takes a ``pytest.MonkeyPatch`` (or anything with its ``setattr``)
and breaks the program until it is undone:

- ``state_unchanged``: the optimizer returns its state as it came;
- ``half_batch``: the loss leaves out the second half of the batch's rows
  and takes the mean over the rest (on two data replicas, also what the
  first reads when the exchange of gradients between them is left out);
- ``exchange_left_out``: the MLP's output is the partial sum that the
  first of two tensor-parallel chips holds before the exchange that adds
  the other's: the down projection over the first half of the hidden
  units only.
"""


def state_unchanged(mp):
    from repro.optimizer import adamw
    mp.setattr(adamw, "adamw_apply",
               lambda params, grads, opt, *a, **kw: (params, opt))


def half_batch(mp):
    import jax
    from repro.models import transformer as tf
    loss_fn = tf.loss_fn

    def half(cfg, params, batch, *a, **kw):
        n = batch["tokens"].shape[0] // 2
        return loss_fn(cfg, params, jax.tree.map(lambda x: x[:n], batch),
                       *a, **kw)
    mp.setattr(tf, "loss_fn", half)


def exchange_left_out(mp):
    from repro.models import layers
    apply_mlp = layers.apply_mlp

    def partial(cfg, params, x):
        half = {**params}
        f = params["wo"].shape[0] // 2
        for k in params:
            if k != "wo":
                half[k] = params[k][..., :f]
        half["wo"] = params["wo"][:f]
        return apply_mlp(cfg, half, x)
    mp.setattr(layers, "apply_mlp", partial)


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "exchange_left_out": exchange_left_out}
