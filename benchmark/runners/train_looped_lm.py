"""Runner for looped LMs (one stack of layers run `total_ut_steps` times
with shared weights, an exit gate and an exit-weighted loss) trained
through `train/lm.py`.

The configuration's file carries the published `config.json` keys; this
maps those that `train_lm` does not know onto `models.looped_lm`'s
arguments and hands the rest to `train_lm`, so that the step, the
optimizer, the batches and the reference's gradient are the other LM
cells' own.  The model owns its loss (`token_losses`), and
`make_lm_train_step` asks it for it.
"""

from __future__ import annotations

from benchmark.runners import train_lm


def model_kwargs(config: dict) -> dict:
    return dict(n_loops=config["total_ut_steps"],
                rope_theta=float(config["rope_theta"]),
                eps=config["rms_norm_eps"],
                init_std=config["initializer_range"],
                exit_beta=config["exit_entropy_beta"])


def build(config: dict, traffic: dict, mesh, reference):
    extra = {**config["model_kwargs"], **model_kwargs(config)}
    return train_lm.build({**config, "model_kwargs": extra}, traffic, mesh,
                          reference)
