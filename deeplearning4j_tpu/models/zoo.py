"""Zoo architectures (sequential ones; DAG models land with ComputationGraph).

Parity targets (reference deeplearning4j-zoo/src/main/java/org/deeplearning4j/zoo/model/):
LeNet.java, SimpleCNN.java, TextGenerationLSTM.java here; AlexNet, VGG16/19,
ResNet50, GoogLeNet, Darknet19, TinyYOLO, InceptionResNetV1 arrive as
ComputationGraph configs.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers import (
    BatchNorm,
    Conv2D,
    Dense,
    DropoutLayer,
    LSTM,
    OutputLayer,
    RnnOutputLayer,
    Subsampling2D,
)
from deeplearning4j_tpu.nn.model import MultiLayerConfiguration


def LeNet5(height: int = 28, width: int = 28, channels: int = 1,
           num_classes: int = 10, updater=None, seed: int = 12345,
           dtype: str = "float32") -> MultiLayerConfiguration:
    """LeNet-5 (zoo/model/LeNet.java): conv5x5x20 - pool - conv5x5x50 - pool -
    dense500 - softmax. BASELINE config #1."""
    return MultiLayerConfiguration(
        layers=(
            Conv2D(n_out=20, kernel=(5, 5), stride=(1, 1), activation="identity",
                   convolution_mode="same"),
            Subsampling2D(kernel=(2, 2), stride=(2, 2), pooling="max"),
            Conv2D(n_out=50, kernel=(5, 5), stride=(1, 1), activation="identity",
                   convolution_mode="same"),
            Subsampling2D(kernel=(2, 2), stride=(2, 2), pooling="max"),
            Dense(n_out=500, activation="relu"),
            OutputLayer(n_out=num_classes, activation="softmax", loss="mcxent"),
        ),
        input_type=InputType.convolutional(height, width, channels),
        updater=updater or {"type": "adam", "lr": 1e-3},
        seed=seed,
        dtype=dtype,
    )


def SimpleCNN(height: int = 48, width: int = 48, channels: int = 3,
              num_classes: int = 10, updater=None, seed: int = 12345) -> MultiLayerConfiguration:
    """SimpleCNN.java: small conv stack with BN + dropout."""
    return MultiLayerConfiguration(
        layers=(
            Conv2D(n_out=16, kernel=(3, 3), activation="relu", convolution_mode="same"),
            BatchNorm(),
            Conv2D(n_out=16, kernel=(3, 3), activation="relu", convolution_mode="same"),
            BatchNorm(),
            Subsampling2D(kernel=(2, 2), stride=(2, 2)),
            Conv2D(n_out=32, kernel=(3, 3), activation="relu", convolution_mode="same"),
            BatchNorm(),
            Conv2D(n_out=32, kernel=(3, 3), activation="relu", convolution_mode="same"),
            BatchNorm(),
            Subsampling2D(kernel=(2, 2), stride=(2, 2)),
            DropoutLayer(dropout=0.5),
            Dense(n_out=256, activation="relu"),
            OutputLayer(n_out=num_classes, activation="softmax"),
        ),
        input_type=InputType.convolutional(height, width, channels),
        updater=updater or {"type": "adam", "lr": 1e-3},
        seed=seed,
    )


def TextGenerationLSTM(vocab_size: int = 77, timesteps: int = 50,
                       hidden: int = 256, updater=None, seed: int = 12345,
                       dtype: str = "float32") -> MultiLayerConfiguration:
    """TextGenerationLSTM.java / GravesLSTM char-RNN (BASELINE config #3):
    2x LSTM(256) + time-distributed softmax, tBPTT."""
    from deeplearning4j_tpu.nn.layers import GravesLSTM

    return MultiLayerConfiguration(
        layers=(
            GravesLSTM(n_out=hidden),
            GravesLSTM(n_out=hidden),
            RnnOutputLayer(n_out=vocab_size, activation="softmax", loss="mcxent"),
        ),
        input_type=InputType.recurrent(vocab_size, timesteps),
        updater=updater or {"type": "rmsprop", "lr": 1e-3},
        seed=seed,
        backprop_type="tbptt",
        tbptt_fwd_length=50,
        tbptt_back_length=50,
        dtype=dtype,
    )


def TransformerLM(vocab_size: int = 256, max_len: int = 512, d_model: int = 256,
                  n_heads: int = 8, n_blocks: int = 4, ffn_mult: int = 4,
                  sequence_parallel: bool = False, moe_experts: int = 0,
                  updater=None, seed: int = 12345,
                  dtype: str = "bfloat16") -> MultiLayerConfiguration:
    """Decoder-only transformer language model — the framework's flagship.

    Beyond-reference capability (the reference has no attention; its text
    model is the GravesLSTM char-RNN). Designed TPU-first: bf16 by default,
    fused qkv/MLP matmuls on the MXU, optional ring-attention sequence
    parallelism (``sequence_parallel=True`` + a mesh with a ``seq`` axis),
    optional MoE blocks (``moe_experts>0``) whose expert axis shards over the
    mesh's ``model`` axis (expert parallelism).
    """
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequence,
        LayerNorm,
        MixtureOfExperts,
        PositionalEmbedding,
        RnnOutputLayer,
        TransformerBlock,
    )

    layers = [
        EmbeddingSequence(n_in=vocab_size, n_out=d_model),
        PositionalEmbedding(max_len=max_len),
    ]
    for i in range(n_blocks):
        layers.append(TransformerBlock(
            n_heads=n_heads, ffn_mult=ffn_mult, causal=True,
            sequence_parallel=sequence_parallel,
        ))
        if moe_experts and i % 2 == 1:  # MoE every second block, switch-style
            layers.append(MixtureOfExperts(n_experts=moe_experts, ffn_mult=ffn_mult))
    layers += [
        LayerNorm(),
        RnnOutputLayer(n_out=vocab_size, activation="softmax", loss="mcxent"),
    ]
    return MultiLayerConfiguration(
        layers=tuple(layers),
        input_type=InputType.recurrent(vocab_size, max_len),
        updater=updater or {"type": "adam", "lr": 3e-4},
        seed=seed,
        dtype=dtype,
    )


def HybridLM(pattern: str, vocab_size: int, d_model: int, max_len: int = 4096,
             mamba: dict = None, attention: dict = None, moe: dict = None,
             eps: float = 1e-5, remat: bool = False, updater=None,
             seed: int = 12345, dtype: str = "float32") -> MultiLayerConfiguration:
    """A hybrid state-space / attention / sparse-expert language model of
    the ``nemotron_h`` kind: an embedding, one ``ResidualBlock`` per letter
    of ``pattern`` (``x <- x + mixer(RMSNorm(x))``), a final RMSNorm and an
    untied, bias-free head. ``M`` is a ``Mamba2Mixer`` built from ``mamba``,
    ``*`` a ``GroupedQueryAttention`` from ``attention`` (no positional
    encoding: the state-space layers carry position), ``E`` a ``SparseMoE``
    from ``moe`` (which says which experts this model holds). ``remat``
    recomputes each layer in the backward pass."""
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequence,
        GroupedQueryAttention,
        Mamba2Mixer,
        ResidualBlock,
        RMSNorm,
        SparseMoE,
    )

    mixers = {"M": lambda: Mamba2Mixer(eps=eps, **(mamba or {})),
              "*": lambda: GroupedQueryAttention(**(attention or {})),
              "E": lambda: SparseMoE(**(moe or {}))}
    unknown = set(pattern) - set(mixers)
    if unknown or not pattern:
        raise ValueError(f"pattern {pattern!r}: letters are M, * and E")
    layers = [EmbeddingSequence(n_in=vocab_size, n_out=d_model)]
    layers += [ResidualBlock(mixer=mixers[c](), eps=eps, remat=remat)
               for c in pattern]
    layers += [RMSNorm(eps=eps),
               RnnOutputLayer(n_out=vocab_size, activation="softmax",
                              loss="mcxent", has_bias=False)]
    return MultiLayerConfiguration(
        layers=tuple(layers),
        input_type=InputType.recurrent(vocab_size, max_len),
        updater=updater or {"type": "adam", "lr": 3e-4},
        seed=seed,
        dtype=dtype,
    )


def LatentAttentionLM(vocab_size: int, d_model: int, n_layers: int,
                      n_dense: int = 1, attention: dict = None,
                      dense_width: int = 0,
                      moe: dict = None, mtp_layers: int = 0,
                      mtp_weight: float = 0.3, eps: float = 1e-6,
                      remat: bool = False, updater=None, seed: int = 12345,
                      dtype: str = "float32") -> MultiLayerConfiguration:
    """A latent-attention sparse-expert language model of the DeepSeek-V2/V3
    kind: an embedding, ``n_layers`` layers of two ``ResidualBlock``s each
    (``x <- x + mixer(RMSNorm(x))``: a ``MultiHeadLatentAttention`` built from
    ``attention``, rotary positions inside it, then a feed-forward: a
    ``GatedMLP`` of ``dense_width`` in the first ``n_dense`` layers, a gated
    ``SparseMoE`` from ``moe`` after them, which says which experts this
    model holds: ``held_start`` / ``n_held``), and an ``MTPOutputLayer``: the
    final RMSNorm, an untied bias-free head and, with ``mtp_layers = 1``, one
    MTP module (a block of the expert kind) whose loss joins the step's at
    ``mtp_weight``. ``remat`` recomputes each block, and each head's logits,
    in the backward pass."""
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequence,
        GatedMLP,
        MTPOutputLayer,
        MultiHeadLatentAttention,
        ResidualBlock,
        SparseMoE,
    )

    if not 0 <= n_dense <= n_layers:
        raise ValueError(f"n_dense={n_dense} of n_layers={n_layers}")
    mla = lambda: MultiHeadLatentAttention(eps=eps, **(attention or {}))  # noqa: E731
    experts = lambda: SparseMoE(gated=True, **(moe or {}))       # noqa: E731
    block = lambda mixer: ResidualBlock(mixer=mixer, eps=eps, remat=remat)  # noqa: E731
    layers = [EmbeddingSequence(n_in=vocab_size, n_out=d_model)]
    for i in range(n_layers):
        layers += [block(mla()), block(GatedMLP(width=dense_width)
                                       if i < n_dense else experts())]
    layers.append(MTPOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent", eps=eps,
        mtp_layers=mtp_layers, mtp_weight=mtp_weight, remat=remat,
        attention=mla() if mtp_layers else None,
        ffn=experts() if mtp_layers else None))
    return MultiLayerConfiguration(
        layers=tuple(layers),
        input_type=InputType.recurrent(vocab_size),
        updater=updater or {"type": "adam", "lr": 3e-4},
        seed=seed,
        dtype=dtype,
    )


def ShortConvLM(layer_types, vocab_size: int, d_model: int, n_dense: int = 0,
                attention: dict = None, conv: dict = None,
                dense_width: int = 0, moe: dict = None, eps: float = 1e-5,
                remat: bool = False, updater=None, seed: int = 12345,
                dtype: str = "float32") -> MultiLayerConfiguration:
    """A gated-short-convolution / attention sparse-expert language model of
    the LFM2 kind: an embedding, one layer per entry of ``layer_types`` made
    of two ``ResidualBlock``s (``x <- x + mixer(RMSNorm(x))``: an operator,
    ``"conv"`` a ``ShortConvMixer`` built from ``conv``, ``"full_attention"``
    a ``GroupedQueryAttention`` from ``attention`` (which says whether q and
    k are normed and turned by their positions); then a feed-forward, a
    ``GatedMLP`` of ``dense_width`` in the first ``n_dense`` layers, a gated
    ``SparseMoE`` from ``moe`` after them, which says which experts this
    model holds), and an ``MTPOutputLayer`` without an MTP module: the final
    RMSNorm and a bias-free head tied to the embedding. ``remat`` recomputes
    each block, and the head's logits, in the backward pass."""
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequence,
        GatedMLP,
        GroupedQueryAttention,
        MTPOutputLayer,
        ResidualBlock,
        ShortConvMixer,
        SparseMoE,
    )

    operators = {
        "conv": lambda: ShortConvMixer(**(conv or {})),
        "full_attention": lambda: GroupedQueryAttention(eps=eps, **(attention or {}))}
    layer_types = tuple(layer_types)
    unknown = set(layer_types) - set(operators)
    if unknown or not layer_types:
        raise ValueError(f"layer_types {layer_types!r}: entries are "
                         f"{sorted(operators)}")
    if not 0 <= n_dense <= len(layer_types):
        raise ValueError(f"n_dense={n_dense} of {len(layer_types)} layers")
    block = lambda mixer: ResidualBlock(mixer=mixer, eps=eps, remat=remat)  # noqa: E731
    layers = [EmbeddingSequence(n_in=vocab_size, n_out=d_model)]
    for i, kind in enumerate(layer_types):
        layers += [block(operators[kind]()),
                   block(GatedMLP(width=dense_width) if i < n_dense
                         else SparseMoE(gated=True, **(moe or {})))]
    layers.append(MTPOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent", eps=eps,
        remat=remat, tied=True))
    return MultiLayerConfiguration(
        layers=tuple(layers),
        input_type=InputType.recurrent(vocab_size),
        updater=updater or {"type": "adam", "lr": 3e-4},
        seed=seed,
        dtype=dtype,
    )
