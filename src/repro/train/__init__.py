from repro.train.trainer import (  # noqa: F401
    TrainState, make_train_step, init_train_state, train_state_shardings,
)
from repro.train.loop import (  # noqa: F401
    FinetuneLoop, FinetuneSettings, expert_sparse_rules, finetune,
)
