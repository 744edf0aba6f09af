from torch_actor_critic_tpu.models.mlp import MLP, torch_linear_bias_init, torch_linear_kernel_init  # noqa: F401
from torch_actor_critic_tpu.models.actor import Actor, DeterministicActor  # noqa: F401
from torch_actor_critic_tpu.models.critic import Critic, DoubleCritic  # noqa: F401
from torch_actor_critic_tpu.models.visual import (  # noqa: F401
    DeterministicVisualActor,
    SimpleCNN,
    VisualActor,
    VisualCritic,
    VisualDoubleCritic,
    conv_output_size,
)
from torch_actor_critic_tpu.models.sequence import (  # noqa: F401
    SequenceActor,
    SequenceCritic,
    SequenceDoubleCritic,
    SequenceTrunk,
    SharedTrunkActor,
    SharedTrunkCritic,
    TrunkSpec,
    policy_params,
)
from torch_actor_critic_tpu.models.multiagent import (  # noqa: F401
    MultiAgentActor,
    MultiAgentDoubleCritic,
)
from torch_actor_critic_tpu.models.taskembed import (  # noqa: F401
    TaskConditionedActor,
    TaskConditionedDoubleCritic,
)
