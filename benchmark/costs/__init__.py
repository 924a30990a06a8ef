"""Operations and bytes a configuration's model requires, from shapes."""
