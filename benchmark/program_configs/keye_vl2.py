"""The program's config of a chip's share, from a configuration's file.

As ``program_configs/qwen3_next.py``: in a file of ``configs/`` the key
``num_experts`` counts the experts held on this chip (it is listed under
``reduced``) and ``num_experts_published`` is the router's width, where
``KeyeVL2Config`` keeps the public config's meaning.  The indexer's sizes
come from the published ``sa_config`` group.
"""


def config(*, num_experts, num_experts_published, experts_held, sa_config,
           **published):
    from paddle_hackathon_tpu.models import KeyeVL2Config
    first, count = experts_held
    if count != num_experts:
        raise ValueError(f"num_experts {num_experts} counts the experts "
                         f"held, experts_held says {count}")
    if sa_config["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
    return KeyeVL2Config(num_experts=num_experts_published,
                         experts_held=(first, count),
                         index_n_heads=sa_config["indexer_num_heads"],
                         index_head_dim=sa_config["indexer_head_dim"],
                         index_topk=sa_config["topk"], **published)
