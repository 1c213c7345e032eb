"""Operations and bytes a served step must spend, counted from the
configuration's sizes and each row's lengths, never from a kernel's
tiles: the count is the same whatever implements the step.

Per token through the model: two operations per weight of every matmul
in the layers (q, k, v, o, gate, up, down), in bf16; the LM head's two
per weight only for a row that emits a token. Attention, in int8: a row
with ``q`` new tokens against a context of ``kv`` tokens (the new ones
included, causal inside the chunk) sees ``q*kv - q*(q-1)/2`` keys, each
costing ``2*hd`` operations for Q·K and ``2*hd`` for A·V per query head.

Bytes a step must read: every weight of the layers and of the LM head
once (the embedding table only by rows, which is left out), plus the
int8 K and V of each live row's context in every layer. A kernel call
must read its int8 q and K/V of the live context and write its int8
output.
"""

from __future__ import annotations

import dataclasses

import numpy as np

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    bias: bool

    @classmethod
    def from_config(cls, conf: dict) -> "Model":
        return cls(d=conf["hidden_size"], heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"], ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   layers=conf["num_hidden_layers"],
                   bias=bool(conf["attention_bias"]))

    @property
    def layer_matmul_weights(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv) + 3 * self.d * self.ff

    @property
    def layer_weights(self) -> int:
        """Matmul weights, biases and the two norm gains of one layer."""
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.bias else 0
        return self.layer_matmul_weights + bias + 2 * self.d

    @property
    def head_weights(self) -> int:
        return self.d * self.vocab + self.d          # LM head + final norm

    @property
    def step_weight_bytes(self) -> int:
        """Weights a step must read once: layers and LM head, in bf16."""
        return BF16 * (self.layers * self.layer_weights + self.head_weights)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim

    def dense_flops(self, tokens, emitting) -> float:
        return 2.0 * (self.layers * self.layer_matmul_weights * tokens
                      + self.d * self.vocab * emitting)

    def visible_keys(self, q, kv):
        q = np.asarray(q, np.float64)
        kv = np.asarray(kv, np.float64)
        return q * kv - q * (q - 1) / 2

    def attn_ops(self, q, kv) -> float:
        """int8 operations of all layers' attention for rows (q, kv)."""
        return float(np.sum(4.0 * self.heads * self.head_dim * self.layers
                            * self.visible_keys(q, kv)))

    def attn_bytes(self, q, kv) -> float:
        """Bytes all layers' attention calls must move for rows (q, kv):
        int8 q in, int8 out, int8 K and V of the context."""
        q = np.asarray(q, np.float64)
        kv = np.asarray(kv, np.float64)
        per_layer = 2 * q * self.heads * self.head_dim \
            + 2 * kv * self.kv_heads * self.head_dim
        return float(np.sum(per_layer) * self.layers)
