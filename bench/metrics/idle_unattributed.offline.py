"""Device: share of the traced closed-loop window in which the device
idles and no program span says why: idle gaps whose innermost host
event is a ``bench:*`` span, ``ming:run`` or none, plus the idle time
outside the ten listed gaps, in %.  Gaps named by the JAX runtime's own
host events (the upload's ``Transpose`` and ``XlaLinearize``,
``DeferredTpuAllocator::Allocate``) are in neither this metric nor
``idle_in_sync.offline``."""
from bench.spans import idle_unattributed_pct


def read(run):
    return idle_unattributed_pct(run)
