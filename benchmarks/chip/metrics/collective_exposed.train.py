"""Share (%) of the traced window in which a collective (all-reduce,
collective-permute, all-gather, reduce-scatter; synchronous, or
asynchronous from start to done) runs on a chip while no compute op does,
averaged over the cell's chips; moves ``train_tokens_per_s``
(``chipbench.trace.collective_exposed``)."""

from chipbench.trace import collective_exposed as read  # noqa: F401
