"""Model zoo: build any assigned architecture from its config."""
from repro.models.transformer import (forward, init_caches, init_model,  # noqa: F401
                                      init_serving_params, loss_fn)
