from repro.models.config import ModelConfig
from repro.models import lm, whisper, paper_models
