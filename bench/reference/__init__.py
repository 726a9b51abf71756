"""Plain references of the configurations the cells run."""
