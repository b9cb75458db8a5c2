"""How often a drafting engine's loop ran one step ahead: ``ahead`` of
``serving.dispatch``, as ``step_ahead_pct.chat`` reads it. A step's rows
take their tokens, drafts and positions from the step before on the device,
so a step that verified drafts holds the next one back no more than any
other."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark.layer_metrics.step_ahead_pct_chat",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "step_ahead_pct.chat.py"))
_chat = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_chat)
read = _chat.read
