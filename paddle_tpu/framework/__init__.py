from .autograd import backward, enable_grad, is_grad_enabled, no_grad, set_grad_enabled
from .core import Tensor, get_device, is_compiled_with_tpu, primitive, set_device, unwrap
from .dtype import convert_dtype, get_default_dtype, set_default_dtype, to_jax_dtype
from .flags import define_flag, flag, get_flags, set_flags
from .random import get_rng_state, host_generator, rng_scope, seed, set_rng_state, split_key
from .selected_rows import SelectedRows
from .string_tensor import FasterTokenizer, StringTensor
