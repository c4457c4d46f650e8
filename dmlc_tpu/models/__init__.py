"""Model layer: the flagship sharded transformer LM.

The reference is a substrate with no models; the TPU rebuild ships one
flagship model family to prove the substrate end-to-end: data flows from
InputSplit partitions through the device feed into a 5-way-parallel
(dp/pp/sp/tp/ep) decoder-only transformer trained with XLA collectives.
"""

from .transformer import (  # noqa: F401
    TransformerConfig,
    count_params,
    decode_flops_per_token,
    flagship_config,
    forward_local,
    forward_prefill,
    forward_prefill_last,
    init_params,
    make_train_step,
    param_specs,
    train_flops_per_token,
    train_step_flops,
    unsharded_loss,
)
