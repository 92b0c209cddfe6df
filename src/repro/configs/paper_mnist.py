"""The paper's own experimental model: 784 -> 200 -> 10 MLP on (synthetic)
MNIST with a 48 x 200 x 10 DQN controlling aggregation frequency (§V).

Not a transformer — the config of core.mlp's model; kept in the registry
so `--arch paper-mnist` selects the paper-faithful experiment scale.
"""
from ..core.dqn import DQNConfig
from ..core.async_fl import AsyncFLConfig

CONFIG = AsyncFLConfig(n_devices=16, n_clusters=4)
SMOKE = AsyncFLConfig(n_devices=4, n_clusters=2, sim_seconds=4.0,
                      local_batch=16)
DQN = DQNConfig()
