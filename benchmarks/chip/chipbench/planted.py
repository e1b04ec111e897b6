"""Faults a ``swirl_trainer_scopes`` cell's check must catch, planted into
the program with a :class:`pytest.MonkeyPatch`.

Each takes the patch and the configuration's model section:

* ``window_on_full``: the full layers masked to the windowed layers'
  window;
* ``plain_rope_on_full``: the full layers given the plain RoPE of
  ``rope_theta`` instead of YaRN;
* ``held_offset_by_one``: the held experts' weights computed as experts
  ``first_expert + 1`` on.
"""

from __future__ import annotations

import dataclasses


def window_on_full(mp, m: dict) -> None:
    import repro.models.layers as layers

    sdpa = layers.sdpa

    def windowed(q, k, v, *, causal, window=0, **kw):
        return sdpa(q, k, v, causal=causal, window=window or m["sliding_window"], **kw)

    mp.setattr(layers, "sdpa", windowed)


def plain_rope_on_full(mp, m: dict) -> None:
    import repro.models.layers as layers

    mp.setattr(
        layers, "layer_rope",
        lambda cfg, local: (layers.rope_freqs(cfg.head_dim, cfg.rope_theta), 1.0),
    )


def held_offset_by_one(mp, m: dict) -> None:
    from chipbench.runners import swirl_trainer

    make = swirl_trainer.model_config

    def shifted(config):
        cfg = make(config)
        moe = dataclasses.replace(cfg.moe, first_expert=cfg.moe.first_expert + 1)
        return dataclasses.replace(cfg, moe=moe)

    mp.setattr(swirl_trainer, "model_config", shifted)


FAULTS = {f.__name__: f for f in (window_on_full, plain_rope_on_full, held_offset_by_one)}
