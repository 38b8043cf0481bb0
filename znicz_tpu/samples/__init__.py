"""Packaged sample models (reference ``samples/`` — SURVEY.md §2.6 L6).

``MANIFESTS`` is the package-metadata registry — the role of the
reference's per-sample ``manifest.json`` (workflow name, config entry
point, published baseline); the CLI's ``--list`` renders it.
"""

#: sample name -> metadata (baselines from BASELINE.md / the reference
#: manifest.json snapshot filenames; None where the reference publishes
#: no number)
MANIFESTS = {
    "wine": {"workflow": "WineWorkflow", "config": "root.wine",
             "baseline": "0.56% err"},
    "mnist": {"workflow": "MnistWorkflow", "config": "root.mnistr",
              "baseline": "1.92% val (MLP) / 0.75% (conv) / "
                          "0.80% (caffe)"},
    "cifar": {"workflow": "CifarWorkflow", "config": "root.cifar",
              "baseline": "17.21% val (caffe) / 45.80% (mlp) / "
                          "9.09% (nin)"},
    "kanji": {"workflow": "KanjiWorkflow", "config": "root.kanji",
              "baseline": "2.74% val"},
    "lines": {"workflow": "LinesWorkflow", "config": "root.lines",
              "baseline": "8.33% val"},
    "yale_faces": {"workflow": "YaleFacesWorkflow",
                   "config": "root.yalefaces", "baseline": "3.59% val"},
    "demo_kohonen": {"workflow": "KohonenWorkflow",
                     "config": "root.kohonen", "baseline": None},
    "mnist_rbm": {"workflow": "MnistRBMWorkflow",
                  "config": "root.mnist_rbm", "baseline": None},
    "approximator": {"workflow": "ApproximatorWorkflow",
                     "config": "root.approximator",
                     "baseline": "MSE 12.81"},
    "sequence": {"workflow": "SequenceWorkflow",
                 "config": "root.sequence",
                 "baseline": None},  # beyond reference scope (scan LSTM)
    "research.mnist_simple": {"workflow": "MnistSimpleWorkflow",
                              "config": "root.mnist_simple",
                              "baseline": "1.48% val"},
    "research.mnist7": {"workflow": "Mnist7Workflow",
                        "config": "root.mnist7",
                        "baseline": "2.83% val / MSE 0.111"},
    "research.wine_relu": {"workflow": "WineReluWorkflow",
                           "config": "root.wine_relu",
                           "baseline": "0.00% train"},
    "research.hands": {"workflow": "HandsWorkflow",
                       "config": "root.hands", "baseline": "8.18% val"},
    "research.tv_channels": {"workflow": "ChannelsWorkflow",
                             "config": "root.channels",
                             "baseline": "0.74% val"},
    "research.mnist_ae": {"workflow": "MnistAEWorkflow",
                          "config": "root.mnist_ae",
                          "baseline": "MSE 0.5478"},
    "research.video_ae": {"workflow": "VideoAEWorkflow",
                          "config": "root.video_ae",
                          "baseline": "MSE 0.26"},
    "research.stl10": {"workflow": "Stl10Workflow", "config": "root.stl",
                       "baseline": "35.10% val"},
    "research.spam_kohonen": {"workflow": "SpamKohonenWorkflow",
                              "config": "root.spam_kohonen",
                              "baseline": None},
    "research.alexnet": {"workflow": "AlexNetWorkflow",
                         "config": "root.alexnet",
                         "baseline": "40.68% val"},
    "research.imagenet_ae": {"workflow": "ImagenetAEWorkflow",
                             "config": "root.imagenet_ae",
                             "baseline": "55.29 pt"},
    "research.looped_lm": {"workflow": "LoopedLMWorkflow",
                           "config": "root.looped_lm",
                           "baseline": None},  # fused path only
    "research.long_context": {"workflow": "(pure-jax ring attention)",
                              "config": "root.long_context",
                              "baseline": None},
}
