"""Core library: the paper's contribution (RMNP) plus the Muon / NorMuon /
Muown / Nora / AdamW family, mixed update strategy, the generic bucketed
engine, schedules and preconditioner diagnostics."""
from repro.core.adamw import adamw  # noqa: F401
from repro.core.bucketing import BucketPlan, build_plan  # noqa: F401
from repro.core.dominance import dominance_ratios, global_dominance  # noqa: F401
from repro.core.engine import BucketedState  # noqa: F401
from repro.core.mixed import (  # noqa: F401
    ClipStats,
    FusedMixedState,
    MixedState,
    clip_by_global_norm,
    is_matrix_param,
    mixed_optimizer,
    momentum_for_diagnostics,
)
from repro.core.muon import newton_schulz  # noqa: F401
from repro.core.registry import make_optimizer, optimizer_names  # noqa: F401
from repro.core.rmnp import rms_lr_scale, row_normalize  # noqa: F401
from repro.core.rules import (  # noqa: F401
    MatrixUpdateRule,
    make_rule,
    per_leaf_reference,
    rule_names,
)
from repro.core.schedule import constant, cosine_with_warmup  # noqa: F401
from repro.core.types import Optimizer, apply_updates  # noqa: F401
