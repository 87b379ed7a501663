"""`paddle.nn.functional` equivalent namespace."""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .attention import (latent_attention, rotary_embedding,  # noqa: F401
                        scaled_dot_product_attention, selected_attention)
from ..decode import beam_search, greedy_search, hsigmoid_loss  # noqa: F401
from ..decode import gather_tree  # noqa: F401
from ...tensor.sequence import sequence_mask  # noqa: F401
