"""The contract's rule on `reduced`, in one place for the manifest tests."""

import re

# what `reduced` may never name: a hidden, intermediate, latent, state or
# projection size, a key that ends in `_dim` or `_rank`, a head size, an
# expansion factor, the experts a token
WIDTH = re.compile(r"(^|_)(hidden|intermediate|latent|state|proj\w*)_size$"
                   r"|_dim$|_rank$|^head_(dim|size)$|expan\w*_factor"
                   r"|^num_experts_per_tok$")
