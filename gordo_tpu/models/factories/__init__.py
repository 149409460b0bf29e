from gordo_tpu.models.factories.backbone import (  # noqa: F401
    afmoe, glm_moe_lite, kimi_linear, lfm2_moe,
)
from gordo_tpu.models.factories.feedforward import (  # noqa: F401
    feedforward_hourglass,
    feedforward_model,
    feedforward_symmetric,
)
from gordo_tpu.models.factories.lstm import (  # noqa: F401
    lstm_hourglass,
    lstm_model,
    lstm_symmetric,
)
from gordo_tpu.models.factories.utils import hourglass_calc_dims  # noqa: F401
