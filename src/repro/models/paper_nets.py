"""The paper's four benchmark networks (TapirXLA §IV): a small CNN, two
LSTMs (LSTM1: isolated digit recognition; LSTM2: continuous speech), and
NCF (neural collaborative filtering, He et al.).

They are the networks of the paper's only performance table, which
compares ``mode="opaque"`` (stock-XLA lowering) with ``mode="tapir"``;
``tests/test_paper_nets.py`` checks both modes agree.  The LSTM cell is
the paper's sweet spot: 8 isolated GEMM library calls vs one fused GEMM
after the added-GEMM + shared-input fusion passes."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import tapir


# ---------------------------------------------------------------------------
# CNN
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CNNConfig:
    hw: int = 28
    in_ch: int = 1
    channels: tuple = (32, 64)
    fc: int = 128
    n_classes: int = 10


class PaperCNN:
    def __init__(self, cfg: CNNConfig = CNNConfig()):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        c1, c2 = cfg.channels
        flat = (cfg.hw // 4) * (cfg.hw // 4) * c2
        init = lambda k, s, fan: jax.random.normal(k, s) / jnp.sqrt(fan)
        return {
            "k1": init(ks[0], (3, 3, cfg.in_ch, c1), 9 * cfg.in_ch),
            "b1": jnp.zeros((c1,)),
            "k2": init(ks[1], (3, 3, c1, c2), 9 * c1),
            "b2": jnp.zeros((c2,)),
            "w3": init(ks[2], (flat, cfg.fc), flat),
            "b3": jnp.zeros((cfg.fc,)),
            "w4": init(ks[3], (cfg.fc, cfg.n_classes), cfg.fc),
            "b4": jnp.zeros((cfg.n_classes,)),
        }

    def forward(self, params, x):
        h = tapir.conv2d(x, params["k1"], params["b1"], activation="relu")
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        h = tapir.conv2d(h, params["k2"], params["b2"], activation="relu")
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        h = h.reshape(h.shape[0], -1)
        return _cnn_fc_head(h, params["w3"], params["b3"],
                            params["w4"], params["b4"])

    def loss(self, params, batch):
        logits = self.forward(params, batch["x"])
        return _xent(logits, batch["y"])


@tapir.parallel_region
def _cnn_fc_head(h, w3, b3, w4, b4):
    # module-level so the program cache keys stably on the call site: both
    # FC layers capture into one region graph (gelu + bias-adds fuse into
    # the GEMM epilogues) and repeat calls replay without re-tracing
    h = tapir.linear(h, w3, b3, activation="gelu")
    return tapir.linear(h, w4, b4)


# ---------------------------------------------------------------------------
# LSTM (LSTM1 / LSTM2 per Braun's benchmark framing)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LSTMConfig:
    input_dim: int = 39
    hidden: int = 256
    n_layers: int = 2
    n_classes: int = 10
    seq_len: int = 80
    per_step_output: bool = False   # LSTM2: per-frame classification


LSTM1 = LSTMConfig()
LSTM2 = LSTMConfig(input_dim=123, hidden=512, n_layers=3, n_classes=61,
                   seq_len=150, per_step_output=True)


class PaperLSTM:
    def __init__(self, cfg: LSTMConfig = LSTM1):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        layers = []
        for li in range(cfg.n_layers):
            key, k1 = jax.random.split(key)
            ind = cfg.input_dim if li == 0 else cfg.hidden
            W = jax.random.normal(k1, (ind + cfg.hidden, 4 * cfg.hidden)) \
                / jnp.sqrt(ind + cfg.hidden)
            layers.append({"W": W, "b": jnp.zeros((4 * cfg.hidden,))})
        key, k2 = jax.random.split(key)
        head = {"w": jax.random.normal(k2, (cfg.hidden, cfg.n_classes))
                / jnp.sqrt(cfg.hidden),
                "b": jnp.zeros((cfg.n_classes,))}
        return {"layers": layers, "head": head}

    def forward(self, params, x):
        """x: [B, T, input_dim]."""
        cfg = self.cfg
        B = x.shape[0]
        h_seq = x
        for li, p in enumerate(params["layers"]):
            def cell(carry, x_t, p=p):
                h, c = carry
                h2, c2 = tapir.lstm_step(x_t, h, c, p["W"], p["b"])
                return (h2, c2), h2
            init = (jnp.zeros((B, cfg.hidden)), jnp.zeros((B, cfg.hidden)))
            (h_fin, _), hs = jax.lax.scan(cell, init,
                                          jnp.moveaxis(h_seq, 0, 1))
            h_seq = jnp.moveaxis(hs, 0, 1)
        if cfg.per_step_output:
            return tapir.linear(h_seq, params["head"]["w"],
                                params["head"]["b"])
        return tapir.linear(h_fin, params["head"]["w"], params["head"]["b"])

    def loss(self, params, batch):
        logits = self.forward(params, batch["x"])
        return _xent(logits, batch["y"])


# ---------------------------------------------------------------------------
# NCF (neural collaborative filtering)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class NCFConfig:
    n_users: int = 6040       # MovieLens-1M
    n_items: int = 3706
    gmf_dim: int = 16
    mlp_dim: int = 32
    mlp_layers: tuple = (64, 32, 16, 8)


class PaperNCF:
    def __init__(self, cfg: NCFConfig = NCFConfig()):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 6 + len(cfg.mlp_layers))
        p = {
            "ug": jax.random.normal(ks[0], (cfg.n_users, cfg.gmf_dim)) * 0.01,
            "ig": jax.random.normal(ks[1], (cfg.n_items, cfg.gmf_dim)) * 0.01,
            "um": jax.random.normal(ks[2], (cfg.n_users, cfg.mlp_dim)) * 0.01,
            "im": jax.random.normal(ks[3], (cfg.n_items, cfg.mlp_dim)) * 0.01,
            "mlp": [],
        }
        ind = 2 * cfg.mlp_dim
        for i, width in enumerate(cfg.mlp_layers):
            p["mlp"].append({
                "w": jax.random.normal(ks[4 + i], (ind, width)) / jnp.sqrt(ind),
                "b": jnp.zeros((width,))})
            ind = width
        p["out_w"] = jax.random.normal(ks[-1],
                                       (cfg.gmf_dim + ind, 1)) * 0.1
        p["out_b"] = jnp.zeros((1,))
        return p

    def forward(self, params, users, items):
        gmf = jnp.take(params["ug"], users, 0) * jnp.take(params["ig"], items, 0)
        h = jnp.concatenate([jnp.take(params["um"], users, 0),
                             jnp.take(params["im"], items, 0)], axis=-1)
        h = _ncf_mlp_tower(h, params["mlp"])
        z = jnp.concatenate([gmf, h], axis=-1)
        return tapir.linear(z, params["out_w"], params["out_b"])[..., 0]

    def loss(self, params, batch):
        logit = self.forward(params, batch["users"], batch["items"])
        y = batch["y"].astype(jnp.float32)
        return jnp.mean(jnp.maximum(logit, 0) - logit * y +
                        jnp.log1p(jnp.exp(-jnp.abs(logit))))


@tapir.parallel_region
def _ncf_mlp_tower(h, mlp_params):
    # module-level for stable program-cache keys: the whole MLP tower is
    # one region — every relu folds into its GEMM's epilogue and the tower
    # runs as a single jit call, replayed without re-tracing
    for lp in mlp_params:
        h = tapir.linear(h, lp["w"], lp["b"], activation="relu")
    return h


def _xent(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
