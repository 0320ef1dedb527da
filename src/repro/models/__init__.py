from repro.models.lm import (  # noqa: F401
    decode_step,
    forward,
    forward_hidden,
    init_decode_state,
    init_paged_state,
    init_params,
    init_params_as,
    loss_fn,
    paged_forward,
    prefill,
)
from repro.models.runtime import Runtime  # noqa: F401
