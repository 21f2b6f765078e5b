from repro.data.synthetic import (uniform_points, gaussian_clusters,
                                  sharded_clusters, drifting_clusters,
                                  labeled_mixture, bayes_labels)

__all__ = ["uniform_points", "gaussian_clusters", "sharded_clusters",
           "drifting_clusters", "labeled_mixture", "bayes_labels"]
