"""Operations and bytes of each test by its definition, per statistic."""
