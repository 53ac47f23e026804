"""Transfer learning, reference-style (SURVEY.md §3.1 / BASELINE config 1).

DeepImageFeaturizer (truncated named model → bottleneck features) feeding
LogisticRegression inside a Pipeline, on a synthetic two-class image set.

Run: python examples/transfer_learning.py
Env: JAX_PLATFORMS=cpu for a quick CPU run; N_IMAGES / MODEL_NAME to scale.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import sparkdl_tpu as sdl
from sparkdl_tpu.image import imageIO


def main():
    n = int(os.environ.get("N_IMAGES", "16"))
    model_name = os.environ.get("MODEL_NAME", "ResNet18")

    # Two synthetic classes: dark images (label 0) vs bright images (1).
    rng = np.random.RandomState(0)
    structs, labels = [], []
    for i in range(n):
        label = i % 2
        base = 40 if label == 0 else 200
        img = np.clip(rng.randint(-30, 30, (64, 64, 3)) + base,
                      0, 255).astype(np.uint8)
        structs.append(imageIO.imageArrayToStruct(img))
        labels.append(label)
    df = sdl.DataFrame.fromPydict({"image": structs, "label": labels},
                                  numPartitions=2)

    featurizer = sdl.DeepImageFeaturizer(
        inputCol="image", outputCol="features", modelName=model_name,
        batchSize=8)
    lr = sdl.LogisticRegression(featuresCol="features", labelCol="label",
                                maxIter=60)
    model = sdl.Pipeline([featurizer, lr]).fit(df)

    preds = model.transform(df).collect()
    acc = np.mean([int(r["prediction"]) == r["label"] for r in preds])
    print(f"{model_name} features -> LogisticRegression: "
          f"train accuracy {acc:.2f} on {n} images")
    assert acc >= 0.75, "separable synthetic classes should fit"


if __name__ == "__main__":
    main()
