"""`tinymoe` pointed at the DENSE block's reference, which is what the harness
used for every architecture before it asked the adapter. Its cell must not
come out correct."""

from benchmark.models.tinymoe import *  # noqa: F401,F403
from benchmark.models.llama import CHECK_LEAVES, reference  # noqa: F401,E402
