from repro.sharding.logical import (  # noqa: F401
    ShardingRules,
    TRAIN_RULES,
    DECODE_RULES,
    current_rules,
    shard,
    spec_for,
    use_rules,
    param_shardings,
)
