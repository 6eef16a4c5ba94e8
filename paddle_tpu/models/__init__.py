from .lenet import LeNet  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig,
    BertModel,
    BertForPretraining,
    BertPretrainingCriterion,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForPretraining,
    ErnieModel,
    ErniePretrainingCriterion,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForPretraining,
    GPTPretrainingCriterion,
)
from .dlrm import (  # noqa: F401
    DLRM,
    DLRMConfig,
    DLRMCriterion,
)
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config,
    SolarOpen2ForCausalLM,
)
from .gigachat3_5 import (  # noqa: F401
    GigaChat35Config,
    GigaChat35ForCausalLM,
)
from .granite_moe_hybrid import (  # noqa: F401
    GraniteMoeHybridConfig,
    GraniteMoeHybridForCausalLM,
)
from .evabyte import (  # noqa: F401
    EvaByteConfig,
    EvaByteForCausalLM,
)
