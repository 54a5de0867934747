from paddlebox_tpu.models.dnn_ctr import DNNCTRModel  # noqa: F401
from paddlebox_tpu.models.deepfm import DeepFMModel  # noqa: F401
from paddlebox_tpu.models.wide_deep import WideDeepModel  # noqa: F401
from paddlebox_tpu.models.dcn import DCNv2Model  # noqa: F401
from paddlebox_tpu.models.dlrm import DLRMModel  # noqa: F401
from paddlebox_tpu.models.mmoe import MMoEModel  # noqa: F401
from paddlebox_tpu.models.pv_rank import PVRankModel  # noqa: F401
from paddlebox_tpu.models.smallthinker import SmallThinkerModel  # noqa: F401
from paddlebox_tpu.models.nemotron_h import NemotronHModel  # noqa: F401
from paddlebox_tpu.models.lfm2_moe import Lfm2MoeModel  # noqa: F401
from paddlebox_tpu.models.deepseek_v3 import DeepseekV3Model  # noqa: F401
from paddlebox_tpu.models.kimi_linear import KimiLinearModel  # noqa: F401

MODEL_REGISTRY = {
    m.name: m for m in (DNNCTRModel, DeepFMModel, WideDeepModel,
                        DCNv2Model, DLRMModel, MMoEModel, PVRankModel,
                        SmallThinkerModel, NemotronHModel, Lfm2MoeModel,
                        DeepseekV3Model, KimiLinearModel)
}
