# The paper's primary contribution — compressed key sort + fast index
# reconstruction — as composable JAX modules. Sibling subpackages hold the
# substrates (models/train/serve/ckpt/data/distributed/launch).

from . import (
    btree,
    compress,
    dbits,
    distsort,
    index,
    keyformat,
    metadata,
    pipeline,
    reconstruct,
    snapshot,
    sortkeys,
    spans,
)

__all__ = [
    "btree",
    "compress",
    "dbits",
    "distsort",
    "index",
    "keyformat",
    "metadata",
    "pipeline",
    "reconstruct",
    "snapshot",
    "sortkeys",
    "spans",
]
