from fast_tffm_tpu.utils.logging import get_logger  # noqa: F401
from fast_tffm_tpu.utils.timing import StepTimer  # noqa: F401
