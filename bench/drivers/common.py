"""What every driver shares: the program's model at a configuration's
sizes, and the interface the harness calls."""

from __future__ import annotations

import gc


def program_model(cfg: dict):
    """The program's ``Model`` for a configuration file: the registry entry
    of ``program_arch`` with every size the file states."""
    from repro.configs import get_config
    from repro.configs.base import AttnConfig
    from repro.models import build_model

    base = get_config(cfg["program_arch"])
    attn = AttnConfig(num_heads=cfg["num_attention_heads"],
                      num_kv_heads=cfg["num_key_value_heads"],
                      head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"])
    mc = base.with_(num_layers=cfg["num_hidden_layers"],
                    d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
                    vocab_size=cfg["vocab_size"], attn=attn,
                    tie_embeddings=cfg["tie_word_embeddings"],
                    norm_eps=cfg["rms_norm_eps"], act=cfg["hidden_act"],
                    param_dtype=cfg["precision"]["params"],
                    dtype=cfg["precision"]["compute"])
    return build_model(mc)


class Driver:
    """``setup()``, ``window(seconds)``, ``traced_steps()``,
    ``layer_inputs(reduction)``, ``release()``, ``check()``.

    ``excluded_s``: seconds of set-up spent taking the check's readings,
    which ``setup_s`` leaves out.  ``fault`` plants one of the faults the
    benchmark's own tests must see fail (None in every measured run)."""

    step_span = "step"

    def __init__(self, cell: dict, cfg: dict, mix: dict, devices, seed: int,
                 log=print, fault: str | None = None):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.devices, self.seed, self.log = devices, seed, log
        self.fault = fault
        self.excluded_s = 0.0

    def release(self):
        gc.collect()
