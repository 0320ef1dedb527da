from repro.models.lm import (  # noqa: F401
    decode_step,
    decode_step_paged,
    forward,
    forward_hidden,
    init_decode_state,
    init_paged_state,
    init_params,
    init_params_as,
    loss_fn,
    prefill,
    prefill_chunk_paged,
    verify_step_paged,
)
from repro.models.runtime import Runtime  # noqa: F401
