"""The output layer of a language model whose head reads other layers'
parameters: the final RMSNorm and the main head, which may be tied to the
embedding (its matrix is the embedding's, transposed), and behind them an
optional multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
section 2.2) that predicts the token after next from the trunk's last hidden
state and the next token's embedding."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import initializers, losses
from deeplearning4j_tpu.nn.config import register_layer
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.normalization import rms_norm
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.nn.layers.residual import ResidualBlock

# the two loss terms of a step, first in the state's "stats" vector (the MTP
# block's feed-forward counters follow) and added up by publish_stats as
# dl4j_<key>_loss_total{layer}
_TERMS = {"main": "next-token loss, summed over the steps whose loss the "
                  "host fetched",
          "mtp": "MTP module's loss before its weight, summed over those steps"}


@register_layer("mtp_output")
@dataclass
class MTPOutputLayer(RnnOutputLayer):
    """``RMSNorm`` -> bias-free head -> loss over [B, T, C], in the last
    place of a stack, fed the trunk's last hidden state ``h`` (the final
    norm is this layer's, because the MTP module reads the state before it).

    With ``mtp_layers = 1`` the step's loss is ``L_main + mtp_weight *
    L_mtp``: ``h'_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh``, one
    block (``ResidualBlock`` around ``attention``, then around ``ffn``) over
    ``h'``, an RMSNorm of its own, and the main head again, scored against
    the labels rolled by one more (the labels are the next tokens ``t_{i+1}``,
    sparse ids [B, T]; the roll's wrap-around position is kept, as the main
    term keeps its own). ``Emb`` is the embedding layer's matrix and the head
    is this layer's ``W``: each is one array read in two places, so
    ``jax.grad`` sums both uses into one gradient and the updater keeps one
    state for it. The embedding is handed in by reference: the layer declares
    it (``shared_params``) and ``MultiLayerNetwork._loss`` passes layer 0's
    parameters to ``score_shared``.

    The layer's state is the MTP block's feed-forward state (an expert
    layer's routing bias) under ``"ffn"``, and a ``"stats"`` vector: the two
    loss terms, then that feed-forward layer's counters; ``fit()`` fetches it
    with the loss and ``publish_stats`` adds it up. With ``mtp_layers = 0``
    there is no second head, no state, and the layer scores as an
    ``RnnOutputLayer`` behind an ``RMSNorm`` does.

    ``tied`` gives the layer no matrix of its own: the logits are ``RMSNorm(h)
    Emb^T`` with ``Emb`` [vocabulary, C] the embedding layer's matrix, handed
    in by reference as for the MTP module, so the one array has one gradient
    (the sum of the look-up's and the head's) and one updater state."""

    has_bias: bool = False
    eps: float = 1e-6
    mtp_layers: int = 0
    mtp_weight: float = 0.3
    attention: Any = None       # a LayerConfig: the MTP block's attention mixer
    ffn: Any = None             # a LayerConfig: its feed-forward mixer
    remat: bool = False
    tied: bool = False          # the head's matrix is the embedding's

    def _blocks(self):
        return tuple(ResidualBlock(mixer=m, eps=self.eps, remat=self.remat)
                     for m in (self.attention, self.ffn))

    def shared_params(self) -> dict:
        """name -> index of the layer whose parameters ``score_shared`` is
        handed under that name; empty where ``score`` is enough."""
        return {"embedding": 0} if self.mtp_layers or self.tied else {}

    def init(self, key, input_type, dtype=jnp.float32):
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers is 0 or 1: one MTP module")
        if self.tied and self.has_bias:
            raise ValueError("a head tied to the embedding has no bias")
        d = input_type.size
        kh, ke, ka, kf = jax.random.split(key, 4)
        gain = lambda: {"gamma": jnp.ones((d,), dtype)}          # noqa: E731
        p = {} if self.tied else super().init(kh, input_type, dtype)
        p = dict(p, norm=gain())
        if self.mtp_layers:
            attn, ffn = self._blocks()
            p["mtp"] = {
                "enorm": gain(), "hnorm": gain(), "norm": gain(),
                "Weh": initializers.initialize(self.weight_init, ke,
                                               (2 * d, d), 2 * d, d, dtype),
                "attn": attn.init(ka, input_type, dtype),
                "ffn": ffn.init(kf, input_type, dtype)}
        return p

    def init_state(self, input_type: InputType):
        if not self.mtp_layers:
            return {}
        ffn = self._blocks()[1].init_state(input_type)
        return {"ffn": ffn, "stats": jnp.zeros(
            (len(_TERMS) + np.size(ffn.get("stats", ())),), jnp.float32)}

    def publish_stats(self, index: int, stats) -> None:
        from deeplearning4j_tpu import obs

        stats = np.asarray(stats)
        for (key, help_), value in zip(_TERMS.items(), stats):
            obs.counter(f"dl4j_{key}_loss_total", help_, ("layer",)).inc(
                float(value), layer=str(index))
        if len(stats) > len(_TERMS):
            self.ffn.publish_stats(index, stats[len(_TERMS):])

    def _matrix(self, params, shared):
        """The head's matrix as it is stored: this layer's [C, vocabulary],
        or the embedding's [vocabulary, C] where the head is tied to it."""
        return shared["embedding"]["W"] if self.tied else params["W"]

    def _logits(self, x, W):
        return x @ (W.T if self.tied else W)

    def preactivation(self, params, x, shared=None):
        x = rms_norm(x, params["norm"]["gamma"], self.eps)
        if self.tied:
            return self._logits(x, self._matrix(params, shared))
        return super().preactivation(params, x)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              shared=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = self.activation_fn()(self.preactivation(params, x, shared))
        return (y if mask is None else y * mask[..., None]), state

    def score_shared(self, params, state, h, labels, *, shared, mask=None,
                     train=True, rng=None):
        """``(loss, new state)`` for the trunk's ``h`` [B, T, C] and
        ``labels`` (sparse [B, T] where there is an MTP module);
        ``shared["embedding"]`` is the embedding layer's parameters."""
        def head(x, gamma, W, y):
            z = self._logits(rms_norm(x, gamma, self.eps), W)
            return losses.average_score(self.loss, y, z, self.activation, mask)

        if self.remat:          # a head's logits are not kept for its backward
            head = jax.checkpoint(head)
        W = self._matrix(params, shared)
        if not self.mtp_layers:
            with jax.named_scope("head"):
                return head(h, params["norm"]["gamma"], W, labels), state
        if labels.ndim != 2:
            raise ValueError("the MTP module reads the next tokens' ids: "
                             "labels must be sparse [B, T]")
        ids = labels.astype(jnp.int32)
        m = params["mtp"]
        d = h.shape[-1]
        with jax.named_scope("head"):
            main = head(h, params["norm"]["gamma"], W, ids)
        with jax.named_scope("mtp"):
            with jax.named_scope("merge"):
                e = jnp.take(shared["embedding"]["W"], ids, axis=0)
                # [e ; h] W_eh as two products: the joined rows are not made
                x = (rms_norm(e, m["enorm"]["gamma"], self.eps) @ m["Weh"][:d]
                     + rms_norm(h, m["hnorm"]["gamma"], self.eps) @ m["Weh"][d:])
            attn, ffn = self._blocks()
            x, _ = attn.apply(m["attn"], {}, x, train=train, rng=rng, mask=mask)
            x, ffn_state = ffn.apply(m["ffn"], state["ffn"], x, train=train,
                                     rng=rng, mask=mask)
            with jax.named_scope("head"):
                mtp = head(x, m["norm"]["gamma"], W, jnp.roll(ids, -1, axis=1))
        stats = jnp.stack([main, mtp]).astype(jnp.float32)
        if "stats" in ffn_state:
            stats = jnp.concatenate([stats, ffn_state["stats"]])
        return (main + self.mtp_weight * mtp,
                {"ffn": ffn_state, "stats": jax.lax.stop_gradient(stats)})
