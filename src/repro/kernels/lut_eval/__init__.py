from .ops import default_interpret, lut_eval_streamed  # noqa: F401
from .ref import lut_eval_gather_ref, lut_eval_ref  # noqa: F401
