"""Test-only reference module: nodes that carry an accelerator count
(``gpu_capacity``, int32) and pods that request accelerators
(``gpu_request``), which the program has no column or field for.  The
harness must turn it away before any device work; its work count reads one
more int32 column a node."""
import numpy as np

from bench.lib.reference import Reference as Base


class Reference(Base):
    COLUMNS = dict(Base.COLUMNS, gpu_capacity=np.int32)
    POD_FIELDS = Base.POD_FIELDS + ("gpu_request",)
    NODE_BYTES = dict(Base.NODE_BYTES, gpu_capacity=4)
