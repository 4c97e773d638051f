"""The program's model config at a configuration file's sizes."""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.core.ssl_pipeline import am_configs
    stu, tea = am_configs(n_layers=cfg["n_layers"],
                          lstm_hidden=cfg["lstm_hidden"],
                          n_senones=cfg["n_senones"],
                          feat_dim=cfg["feat_dim"])
    return tea if cfg["bidirectional"] else stu
