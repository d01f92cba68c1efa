"""The paper's WSI analysis pipeline (segmentation + features)."""
from repro.pipeline.synth import make_slide, make_tile
from repro.pipeline.wsi import (
    FeatureStage,
    SegmentationStage,
    analyze_tile,
    compute_features,
    extract_object_rois,
    make_wsi_storage,
    segment_tile,
    split_nuclei,
)

__all__ = [
    "make_slide",
    "make_tile",
    "FeatureStage",
    "SegmentationStage",
    "analyze_tile",
    "compute_features",
    "extract_object_rois",
    "make_wsi_storage",
    "segment_tile",
    "split_nuclei",
]
